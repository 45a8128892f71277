"""The worst single tick's lag of the window, as ``flush_lag_ms``
takes it."""
LAYER = "swap and flush readout"
UNIT = "ms"
MOVES = "flush_lag_ms"


def read(run):
    lags = run["lags"][run["lag_of"]]
    return 1e3 * max(lags) if lags else None
