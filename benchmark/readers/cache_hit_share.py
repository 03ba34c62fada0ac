"""cache_hit_share: executables taken from a persistent cache over those
taken or compiled, in percent (``Compile/phase_ms{phase=load}`` count over
it and ``{phase=compile}``): 100 = the run started warm."""
from benchmark.readers import setup_phases


def read(record, metric):
    loads = setup_phases.count("Compile/phase_ms", "phase", ("load",))
    total = loads + setup_phases.count("Compile/phase_ms", "phase",
                                       ("compile",))
    return 100.0 * loads / total if total else None
