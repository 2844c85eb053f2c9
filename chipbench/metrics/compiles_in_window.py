"""Backend compiles that ended inside the window (persistent-cache loads
included): should be 0."""


def read(run):
    return float(run.compiles_in_window)
