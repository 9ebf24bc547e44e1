"""setup_s: seconds from the process's start to the window's opening —
weights made, the cell's programs loaded or compiled and warmed, and for
a backlog the slots filled.  Host clock."""


def read(ctx):
    return ctx.setup_s
