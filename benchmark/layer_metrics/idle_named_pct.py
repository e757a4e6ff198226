"""Share of the traced window's device-idle time that falls in a program
stage: each idle gap charged to the innermost host span covering its
midpoint (trace_reduce.Trace.idle_gaps), over the benchmark's spans and
the program's `cephtrace:` spans together (program_spans.py); the
percentage charged to a `cephtrace:` span."""
import program_spans


def read(ctx, metric):
    spans = program_spans.load(ctx)
    if not spans or not ctx.trace.devices:
        ctx.log(f"{metric['name']}: no program span or no device plane")
        return None
    gaps = program_spans.attribution(ctx.trace, spans).idle_gaps(
        len(spans) + len(ctx.trace.spans) + 1)
    idle = sum(s for _, s in gaps)
    named = sum(s for who, s in gaps
                if who.startswith(program_spans.PREFIX))
    ctx.log(f"{metric['name']}: {named:.6f} of {idle:.6f} s idle in "
            f"program stages; by span {gaps[:8]}")
    return 100.0 * named / idle if idle else None
