"""Host milliseconds per call of one program stage: the self time of
the program's `cephtrace:<stage>` spans named `<stage>` or `<stage>.*`
in the traced window (program_spans.py), over the window's calls.  Self
time leaves out the spans nested in a span on its thread, so `stage_ms.ec`
is the plugin's own host work, without the `gf.*` stages inside it."""
import program_spans


def read(ctx, metric):
    stage = metric["name"].split(".", 1)[1]
    spans = program_spans.load(ctx)
    if spans is None:
        return None
    own = [ns for (name, _, _, _), ns in zip(spans,
                                             program_spans.self_ns(spans))
           if program_spans.stage_of(name, stage)]
    calls = ctx.counters.get("calls", 0)
    if not own or not calls:
        ctx.log(f"{metric['name']}: no {stage!r} span in the window")
        return None
    ctx.log(f"{metric['name']}: {len(own)} spans, {sum(own) / 1e9:.6f} s "
            f"self time over {calls} calls")
    return sum(own) / 1e6 / calls
