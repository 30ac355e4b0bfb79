"""Set-up: process start to the window's start, warm-up included (host
clock)."""


def read(ctx):
    return ctx["setup_s"]
