"""Set-up: process start to window start (data, design build, compile or
compile-cache loads, warm-up).  Host clock."""


def read(run):
    return run.setup_s
