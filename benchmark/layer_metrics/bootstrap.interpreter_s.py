"""Worker bootstrap: ``since_process_start_s`` of the resumed incarnation's
``bootstrap.process_start`` event (``trainer/bootstrap.py::init``): the new
interpreter's start and the imports before ``bootstrap.init``, by the
worker's own clock against its process's start time in /proc."""
from benchmark.harness import obs_read

LAYER = "worker bootstrap"
SOURCE = "program_span"


def read(spans, trace, counters):
    starts = [r for r in obs_read.last_incarnation(obs_read.records(spans))
              if r.get("kind") == "bootstrap.process_start"]
    return starts[0].get("since_process_start_s") if starts else None
