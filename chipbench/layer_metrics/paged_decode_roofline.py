"""paged_decode_roofline (%, kernels): the least time the chip could take
for the paged decode attention of the traced steps (the larger of its
FLOPs over peak FLOP/s and the bytes of K and V the live rows' contexts
need over peak bytes/s, contexts taken from the client's own per-token
records) over the device time of the paged attention kernel.  Moves
itl_p95_s."""
import counts
import tracefile
import work

KERNEL = r"^%paged_decode_attention\b"


def read(run):
    if run.trace is None:
        return None
    t = tracefile.op_seconds(run.trace, KERNEL)
    flops = nbytes = 0.0
    for kind, r, *a in work.traced_items(run):
        if kind == "decode":
            f, b = counts.paged_decode_kernel(run.m, a[0])
            flops, nbytes = flops + f, nbytes + b
    if t <= 0 or flops <= 0:
        return None
    least = max(flops / run.peaks["bf16_flops_per_s"],
                nbytes / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / t
