"""Set-up seconds: process start to the window's start (imports, matrix,
plan build, compile or compile-cache load, warm-up), host clock."""


def read(run):
    return run.setup_s
