"""Device nanoseconds of the straw2 scoring kernel per PG mapped: the
kernel's events in the trace over the PGs the window mapped.  Not a
roofline share: the kernel is bound by int32 vector work, for which the
v5e publishes no peak."""
from trace_reduce import KernelMissing

#: the Pallas straw2 scorer (ceph_tpu/ops/pallas_crush.py)
KERNEL = r"straw2_scores_pallas[.\d]* = .*custom-call\("


def read(ctx, metric):
    try:
        secs, names = ctx.trace.kernel(KERNEL)
    except KernelMissing as e:
        ctx.log(f"{metric['name']}: {e}")
        return None
    ctx.log(f"{metric['name']}: kernel ops {names}, {secs:.6f} s")
    pgs = ctx.counters.get("pgs_mapped", 0)
    return secs * 1e9 / pgs if pgs else None
