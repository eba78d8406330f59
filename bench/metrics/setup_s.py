"""setup_s: process start to the start of the window (s): JAX and chip
init, problem build, optimize, compile or cache load, the warm call;
less the host seconds the plain reference spent on the right-hand
sides."""


def read(ctx):
    return ctx.setup["setup_s"]
