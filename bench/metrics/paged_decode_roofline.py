"""Roofline share of the Pallas paged decode kernel, in %: the least time
to move the bytes it must (live K/V pages, q and out of each active lane,
every layer) at peak HBM bandwidth, over its device time.  The kernel is
bound by bandwidth: its FLOPs are 4 H hd per cached position, far under
the bytes' time at 197 TFLOP/s."""
from bench import flops, readers


def read(run):
    ops = [v for k, v in run.trace.ops.items()
           if k.startswith("paged_flash_decode")]
    dev = sum(s for _, s in ops)
    rounds = readers.decode_rounds(run)
    if not dev or not rounds:
        return None
    ps = run.info["page_size"]
    need = sum(flops.paged_kernel_bytes(run.model, r, ps) for r in rounds)
    return 100.0 * need / run.peaks["hbm_bytes_per_s"] / dev
