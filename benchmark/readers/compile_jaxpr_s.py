"""compile_jaxpr_s: seconds of Python tracing to a jaxpr, every executable
of the run (``Compile/phase_ms{phase=jaxpr}``)."""
from benchmark.readers import setup_phases


def read(record, metric):
    return setup_phases.phases_s("jaxpr")
