"""Share of the traced window in which no operation ran on the device:
1 - busy / window, busy being the union of the device's operation
intervals (averaged over the chips used)."""


def read(ctx, metric):
    if not ctx.trace.devices:
        ctx.log(f"{metric['name']}: no device plane in the trace")
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s() / ctx.trace.window_s())
