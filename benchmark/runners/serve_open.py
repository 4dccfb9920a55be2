"""Runner kind ``serve_open``: ``serve.py`` runs it (it reads the kind from
the traffic file)."""

from benchmark.runners.serve import run  # noqa: F401
