"""``model=<file>.py`` fixture for launch-string tests: frames pass through
one jitted program unchanged."""

from nnstreamer_tpu.backends.jax_backend import JaxModel


def get_model():
    return JaxModel(apply=lambda params, x: x, name="identity")
