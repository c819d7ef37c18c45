"""Share of the traced serving window in which no op ran on the chip."""
from bench.measure import idle_share


def read(run):
    return idle_share(run)
