"""Share of the traced window, in percent, in which chip 0 was idle while the
host was inside a stage executor's call or a link codec's (``seifer.stage``
or ``seifer.codec`` the innermost open program span)."""

from bench import spans

RESULTS = spans.results_dir(__file__)


def read(run):
    return spans.idle_share(run.device, spans.program_spans(run, RESULTS), spans.DISPATCH)
