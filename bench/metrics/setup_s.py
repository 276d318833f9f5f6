"""``setup_s``: seconds from process start to the first timed job
(imports, generation, the program's graph build and H2D, compile or cache
load, warm-up)."""


def read(run):
    return run.setup_s
