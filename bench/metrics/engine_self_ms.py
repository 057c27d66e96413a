"""Host time of the serving engine itself per step, in ms: the mean over the
outermost ``seifer.step`` spans in the traced window of each step's duration
less what the stage, codec and garbage-collection spans inside it cover."""

from bench import spans

RESULTS = spans.results_dir(__file__)


def read(run):
    found = spans.window_spans(run.device, spans.program_spans(run, RESULTS))
    if found is None:
        return None
    return spans.mean(spans.self_ms(found, spans.STEP, (*spans.DISPATCH, spans.GC)))
