"""Share of its roofline that the GF(2^8) apply kernel reached: the least
time the applies of the traced window need (roofline.gf_apply_min_s over
the shapes the driver applied, unpadded) over the device time of the
kernel's events in the trace."""
from roofline import gf_apply_min_s
from trace_reduce import KernelMissing

#: the fused Pallas apply (ceph_tpu/ops/pallas_gf.py) shows in the trace
#: as a custom call named after its jitted wrapper
KERNEL = r"_apply_grouped[.\d]* = .*custom-call\("


def read(ctx, metric):
    try:
        secs, names = ctx.trace.kernel(KERNEL)
    except KernelMissing as e:
        ctx.log(f"{metric['name']}: {e}")
        return None
    ctx.log(f"{metric['name']}: kernel ops {names}, {secs:.6f} s")
    need = sum(count * gf_apply_min_s(rows, n, length, ctx.device_kind)
               for rows, n, length, count in ctx.counters["gf_applies"])
    return 100.0 * need / secs
