"""assign.compiles_per_request: XLA compilations in the window per request,
counted from JAX's own monitoring events (``yardstick.instrument``)."""
from yardstick import layers


def read(view):
    if view.compiles is None or not layers.n_requests(view):
        return None
    return view.compiles.compiled / layers.n_requests(view)
