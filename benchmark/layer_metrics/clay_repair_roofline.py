"""Share of the HBM roofline that the traced window's Clay repairs
reached: the least bytes the repairs must move (repair_bytes over the
shapes the driver repaired) over peak HBM bandwidth, over the device's
busy time in the window.  Busy time, not one kernel's events, so that
the share reads the same work whatever implements the repair."""
from roofline import peaks


def repair_bytes(d: int, Z: int, q: int, sub_len: int, stripes: int) -> int:
    """HBM bytes a single-shard repair needs at the least: the d
    helpers' Z/q repair-plane sub-chunks read once and the lost chunk's
    Z sub-chunks written once, for each stripe."""
    return (d * Z // q + Z) * sub_len * stripes


def read(ctx, metric):
    shapes = ctx.counters.get("clay_repairs")
    if not shapes or not ctx.trace.devices:
        ctx.log(f"{metric['name']}: no repair counted or no device plane")
        return None
    busy = ctx.trace.busy_s()
    need = sum(count * repair_bytes(d, Z, q, sub_len, stripes)
               for d, Z, q, sub_len, stripes, count in shapes)
    ctx.log(f"{metric['name']}: {need} B least, {busy:.6f} s busy")
    if not busy:
        return None
    return 100.0 * need / peaks(ctx.device_kind)["hbm_bytes_per_s"] / busy
