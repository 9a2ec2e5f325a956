"""Device time per launch, in ms, of the ops under the program's ``dense/gru`` scope (the interest-extractor GRU: its loop and the products hoisted out of it)."""
from chipbench import readings


def read(run):
    return readings.scope_ms(run, 'dense/gru')
