"""Worker bootstrap: worker clock from process start to ``jax.devices()``
returned (in the elastic cell: of the resumed incarnation)."""
LAYER = "worker bootstrap"
SOURCE = "host_clock"


def read(spans, trace, counters):
    return spans.get("device_open_s")
