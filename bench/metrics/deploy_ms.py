"""Wall time of the ``deploy(spec)`` call in set-up: validation, probing,
planning, placement and the executors' binding."""


def read(run):
    return run.deploy_ms
