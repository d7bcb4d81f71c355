"""Backend compilations (``jax.monitoring``) that ended inside the window of a
serving cell. Must read 0: a compile in the window is set-up paid there."""


def read(run):
    return float(len(run["compiles"].between(*run["window"])))
