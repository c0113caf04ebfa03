"""Engine and executor: host ms per engine step outside its end-of-step
wait, (``engine.step`` - ``engine.barrier``) seconds over the
``engine.step`` count, from the program's spans in the trace
(``bench/trace_layers.py``; saturated cell)."""

from bench.trace_layers import step_host_ms as read  # noqa: F401
