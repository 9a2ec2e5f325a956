"""Device time per launch, in ms, of the ops under the program's ``sparse/pool`` scope (the masked sum of each bag of G_s)."""
from chipbench import readings


def read(run):
    return readings.scope_ms(run, 'sparse/pool')
