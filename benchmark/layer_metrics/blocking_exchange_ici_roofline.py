"""``blocking_exchange_ici_roofline``: the exchange's share of its roofline,
in percent. The floor: of a chip's ``nnz / k`` entries, ``(k - 1) / k`` belong
to the other chips' user blocks and must leave it, 12 B each (user row, item
row, value: what ``counts.sweep_min_bytes`` counts a rating), and as many
arrive; a chip's links carry 1,600 Gbit/s, taken as 200 GB/s each way (the
least floor, so the share cannot pass 100% for want of a direction). The
time: ``blocking_exchange_s``. None without that time."""

from benchmark.layer_metrics import blocking_exchange_s

# Google Cloud documentation, "TPU v5e" system architecture: interchip
# interconnect 1,600 Gbit/s a chip (benchmark/peaks.json's source; this
# PR may not edit that table)
ICI_BYTES_PER_S = 1600e9 / 8
BYTES_PER_ENTRY = 12


def read(ctx):
    seconds = blocking_exchange_s.read(ctx)
    sizes = ctx.get("sizes") or {}
    k = sizes.get("num_blocks")
    if not seconds or not k or k < 2 or ctx.get("peaks") is None:
        return None
    leaving = sizes["nnz_train"] / k * (k - 1) / k
    floor = leaving * BYTES_PER_ENTRY / ICI_BYTES_PER_S
    return 100.0 * floor / seconds
