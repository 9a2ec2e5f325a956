"""Device time per launch, in ms, of the ops under the program's ``sparse/gather`` scope (the row fetch of G_s)."""
from chipbench import readings


def read(run):
    return readings.scope_ms(run, 'sparse/gather')
