"""Worker bootstrap: worker clock from process start to the moment it asks
JAX for its devices — imports and the compile cache's set-up.  The first
``jax.devices()`` itself (``backend_open_s``: the runtime's start-up) is
left out, as it is out of ``setup_s`` and the kill-to-step seconds; in the elastic cell
of the resumed incarnation."""
LAYER = "worker bootstrap"
SOURCE = "host_clock"


def read(spans, trace, counters):
    if "device_open_s" not in spans or "backend_open_s" not in spans:
        return None
    return spans["device_open_s"] - spans["backend_open_s"]
