"""Host wall time of the blocked index build in set-up; the program's
modules are imported before it, with the configuration's system."""


def read(ctx):
    return ctx.build_s
