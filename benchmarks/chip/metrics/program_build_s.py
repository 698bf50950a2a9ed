"""Host seconds the engine spent building (tracing and compiling, or
loading from the compile cache) its programs before the window opened:
the engine's ``program_build_s`` counter at the window's opening."""


def read(rec):
    return rec["stats_open"].get("program_build_s")
