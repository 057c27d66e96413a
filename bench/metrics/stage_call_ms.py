"""Mean host time of one stage executor's call, in ms: the ``seifer.stage``
spans that start in the traced window, waits inside the dispatch included."""

from bench import spans

RESULTS = spans.results_dir(__file__)


def read(run):
    found = spans.window_spans(run.device, spans.program_spans(run, RESULTS))
    if found is None:
        return None
    return spans.mean(spans.durations_ms(found, spans.STAGE))
