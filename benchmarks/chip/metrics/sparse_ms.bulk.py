"""Device time per launch, in ms, of the ops the compiled step attributes to the sparse layer (models/embedding.py)."""
from chipbench import readings


def read(run):
    return readings.layer_ms(run, 'sparse')
