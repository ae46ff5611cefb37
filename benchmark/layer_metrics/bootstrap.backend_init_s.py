"""Worker bootstrap: the ``bootstrap.backend_init`` span — the first
``jax.devices()`` of the process, which initialises the backend and takes
the chip (``common/jax_env.py::device_summary``); in the elastic cell of
the resumed incarnation.  What of ``bootstrap.device_open_s`` is JAX's own
start-up and not imports or the launcher's hand-over."""
from benchmark.harness import obs_read

LAYER = "worker bootstrap"
SOURCE = "program_span"


def read(spans, trace, counters):
    recs = obs_read.last_incarnation(obs_read.records(spans))
    first = [s for s in obs_read.named(recs, "bootstrap.backend_init")
             if (s.get("args") or {}).get("first")]
    return obs_read.seconds(first[:1])
