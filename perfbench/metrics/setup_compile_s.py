"""Backend compile seconds during set-up, from JAX's monitoring event; a
persistent-cache hit is not a backend compile."""


def read(run):
    return run["compile"]["setup_s"]
