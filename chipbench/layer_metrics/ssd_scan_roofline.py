"""ssd_scan_roofline (%, kernels): the least time the chip could take for
the chunked SSD scans of the traced steps, counting only the true prompt
tokens (so bucket padding shows), over the device time of the SSD scan
kernel.  Moves itl_p95_s."""
import counts
import tracefile
import work

KERNEL = r"^%ssd_chunked_scan\b"


def read(run):
    if run.trace is None:
        return None
    t = tracefile.op_seconds(run.trace, KERNEL)
    chunk = run.m["ssm_chunk"]
    flops = nbytes = 0.0
    for kind, r, *a in work.traced_items(run):
        if kind == "prefill":
            # the program's scan chunk is the bucket when it is shorter
            n = a[1] - a[0]
            b = next(x for x in run.conf["engine"]["buckets"] if x >= n) \
                if n <= run.conf["engine"]["buckets"][-1] else chunk
            f, by = counts.ssd_scan_kernel(run.m, n, min(chunk, b))
            flops, nbytes = flops + f, nbytes + by
    if t <= 0 or flops <= 0:
        return None
    least = max(flops / run.peaks["bf16_flops_per_s"],
                nbytes / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / t
