"""Device time per launch, in ms, of the ops the compiled step attributes to the dense layer (models/dlrm.py, models/layers.py)."""
from chipbench import readings


def read(run):
    return readings.layer_ms(run, 'dense')
