"""Device time per launch, in ms, of the ops under the program's ``dense/augru`` scope (the AUGRU of interest evolution: its loop and the products hoisted out of it)."""
from chipbench import readings


def read(run):
    return readings.scope_ms(run, 'dense/augru')
