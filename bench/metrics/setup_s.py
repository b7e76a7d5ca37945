"""setup_s: seconds from process start to the end of the warm-up pass
(imports, compile-cache loading or compiling, the warm-up traffic)."""


def read(run):
    return run.setup_s
