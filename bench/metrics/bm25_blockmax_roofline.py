"""Share of the HBM roofline reached by the Pallas BM25 scoring kernels,
in percent. Work is counted the same whatever implements it: the blocks
the host's pruning decision kept over the traced span (probes once,
before bucket padding) times the mean compressed bytes of a block (its
doc-delta and tf bit planes plus its metadata). The least time for that
work is its bytes over the chip's HBM bandwidth; the kernels' operations
per byte are few, so HBM is the binding roof. The share is that least
time over the kernels' device time in the trace."""
from lib.readers import KERNEL_OPS


def read(ctx):
    t, c = ctx.trace, ctx.counters
    if t is None or ctx.peaks is None or not c.get("traced_blocks_survived"):
        return None
    kernel_s = t.ops_matching(KERNEL_OPS)
    if kernel_s <= 0:
        return None
    least_s = c["traced_blocks_survived"] * ctx.block_bytes \
        / ctx.peaks["hbm_bw"]
    return 100.0 * least_s / kernel_s
