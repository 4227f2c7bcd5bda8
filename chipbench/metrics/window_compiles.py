"""Compiles inside the traced window: the program's own ``compile.events``
counter (``fl/compile_watch.py``: JAX traces and backend compiles while
the engine runs with a tracer), between the first and last mark.  None
where the program keeps no such counter."""


def read(art):
    return art.get("window_compiles")
