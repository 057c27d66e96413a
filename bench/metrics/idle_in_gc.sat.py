"""Share of the traced window, in percent, in which chip 0 was idle while the
host collected garbage (``seifer.gc`` the innermost open program span)."""

from bench import spans

RESULTS = spans.results_dir(__file__)


def read(run):
    return spans.idle_share(run.device, spans.program_spans(run, RESULTS), (spans.GC,))
