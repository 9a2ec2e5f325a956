"""Device time per launch, in ms, of the ops under the program's ``dense/attention`` scope and below it (the attention unit's MLP, ``dense/attention/mlp``, and the softmax)."""
from chipbench import readings


def read(run):
    if run.trace is None:
        return None
    paths = [p for p in run.trace.scope_s if p == "dense/attention" or p.startswith("dense/attention/")]
    parts = [ms for p in paths if (ms := readings.scope_ms(run, p)) is not None]
    return sum(parts) if parts else None
