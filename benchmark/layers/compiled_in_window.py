"""Layer: process start, compile cache. Programs compiled inside the
window: the entries the persistent compile cache gained between the
window's first statement and its last answer (`benchlib/harness.py`; a
compile under `jax_persistent_cache_min_compile_time_secs` leaves none).
A count; 0 is what a warmed window reads."""


def read(run):
    if "statements" not in run:
        return None
    return run.get("compiled_in_window")
