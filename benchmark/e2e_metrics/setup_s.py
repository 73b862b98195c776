"""Seconds from process start to the first measured pass or epoch:
imports, pass files, native library, compile-cache loads (compilation in
a checkout's first run), the reference check and the warm-up."""


def read(run):
    return run.setup_s
