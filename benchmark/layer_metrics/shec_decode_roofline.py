"""Share of the HBM roofline that the traced window's SHEC rebuilds
reached: the least bytes the rebuilds must move (rebuild_bytes over the
read sets the reference finds least, for the shards the driver rebuilt)
over peak HBM bandwidth, over the device's busy time in the window.
Busy time, not one kernel's events, so that the share reads the same
work whatever implements the rebuild."""
from roofline import peaks


def rebuild_bytes(reads: int, length: int) -> int:
    """HBM bytes one single-chunk rebuild needs at the least: each of the
    `reads` chunks of its least read set read once and the lost chunk
    written once, `length` bytes each."""
    return (reads + 1) * length


def read(ctx, metric):
    shapes = ctx.counters.get("shec_decodes")
    if not shapes or not ctx.trace.devices:
        ctx.log(f"{metric['name']}: no rebuild counted or no device plane")
        return None
    busy = ctx.trace.busy_s()
    need = sum(count * rebuild_bytes(reads, length)
               for reads, length, count in shapes)
    ctx.log(f"{metric['name']}: {need} B least, {busy:.6f} s busy")
    if not busy:
        return None
    return 100.0 * need / peaks(ctx.device_kind)["hbm_bytes_per_s"] / busy
