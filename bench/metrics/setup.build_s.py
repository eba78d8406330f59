"""setup.build_s: host seconds of hpcg.generate_problem."""


def read(ctx):
    return ctx.setup["build_s"]
