"""Device time per traced round of the server update: the optimizer step,
the strategy's post-round hook and the round's metrics; the ops whose
innermost named scope is `fedround.server_update` (`bench/scopes.py`)."""
from bench import scopes


def read(run):
    return scopes.per_round_ms(run, "fedround.server_update")
