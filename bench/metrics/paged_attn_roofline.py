"""kernels/paged_attn: share of the HBM roofline.  The bytes the
algorithm must read (each live key and value row of every decode step
of the traced group, all layers) over the chip's HBM bandwidth, over
the summed device time of the kernel's events.  Bound by bytes: the
kernel does ~2 FLOPs per byte read."""
from bench import readers


def read(run):
    s = readers.traced_summary(run)
    t = s.op_s(readers.PAGED_ATTN_KERNEL) if s else None
    if not t or run.peaks is None:
        return None
    need = readers.served_kv_bytes(run, run.traced["requests"])
    return 100.0 * need / run.peaks.hbm_bytes_s / t
