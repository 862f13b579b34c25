"""Seconds the stitch compiler took to plan and emit the program
(``CompileStats.compile_time_s``, all passes); moves ``setup_s``."""


def read(run):
    c = run.get("counters", {})
    return c.get("compile_time_s")
