"""compile_cache_io_s: seconds of persistent-cache I/O: the executable
store's verified reads and its stores (``Compile/phase_ms{phase=read}``,
``{phase=store}``), and XLA's own retrievals from its compilation cache
(``Compile/xla_cache_read_ms``).  0 where the program timed its
executables and none of them touched a cache file."""
from benchmark.readers import setup_phases


def read(record, metric):
    if not setup_phases.count("Compile/phase_ms", "phase", ("jaxpr",)):
        return None
    from bigdl_tpu import telemetry
    try:
        xla_ms = telemetry.histogram("Compile/xla_cache_read_ms").sum
    except TypeError:
        xla_ms = 0.0
    return (setup_phases.total_s("Compile/phase_ms", "phase",
                                 ("read", "store")) + xla_ms / 1e3)
