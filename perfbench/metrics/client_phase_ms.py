"""Device time per traced round of the round program's client phase: the
server's download mask and plans, then every client's download and local
training.  Ops whose innermost named scope is `fedround.clients`, so the
upload transform nested in it (`upload_ms`) is left out
(`bench/scopes.py`)."""
from bench import scopes


def read(run):
    return scopes.per_round_ms(run, "fedround.clients")
