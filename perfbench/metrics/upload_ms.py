"""Device time per traced round of the upload transform: every client's
selection, quantization and coding of its update, the ops whose
innermost named scope is `fedround.upload` (`bench/scopes.py`)."""
from bench import scopes


def read(run):
    return scopes.per_round_ms(run, "fedround.upload")
