"""Device time per launch, in ms, of the ops under the program's ``sparse`` scope (the history, target and profile row gathers)."""
from chipbench import readings


def read(run):
    return readings.scope_ms(run, 'sparse')
