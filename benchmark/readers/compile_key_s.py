"""compile_key_s: seconds of the StableHLO text, its digest and the store's
entry key, every executable of the run (``Compile/phase_ms{phase=key}``)."""
from benchmark.readers import setup_phases


def read(record, metric):
    return setup_phases.phases_s("key")
