from .serializer import save_model, load_model
from .gradient_check import check_gradients


def device_iteration(net, advance: int):
    """Device-resident iteration counter shared by MultiLayerNetwork and
    ComputationGraph: a fresh host-scalar upload per step serializes a
    host→device transfer into every dispatch, so the counter lives on
    device and advances with an (async) eager add.  Falls back to an upload
    whenever python-side ``net.iteration`` was changed externally
    (checkpoint restore, manual reset)."""
    import jax.numpy as jnp
    if net._it_dev is None or net._it_dev_val != net.iteration:
        net._it_dev = jnp.asarray(net.iteration, jnp.int32)
    it = net._it_dev
    net._it_dev = it + advance
    net._it_dev_val = net.iteration + advance
    return it
