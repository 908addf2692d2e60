"""Device time per traced round of the server's aggregation: the sparse
path's pack and scatter-add, or the dense rule; the ops whose innermost
named scope is `fedround.aggregate` (`bench/scopes.py`)."""
from bench import scopes


def read(run):
    return scopes.per_round_ms(run, "fedround.aggregate")
