"""program_setup_s: seconds the program's set-up entry points took, over
every call in the run (``Setup/program_ms``: the LM engines' construction
and warm-up, ``optimize()`` to step 1's dispatch)."""
from benchmark.readers import setup_phases


def read(record, metric):
    if not setup_phases.count("Setup/program_ms", "entry",
                              setup_phases.ENTRIES):
        return None
    return setup_phases.total_s("Setup/program_ms", "entry",
                                setup_phases.ENTRIES)
