"""Host seconds of the first warm-up request, which builds and compiles the
entry's program (or loads it from the persistent cache), ended with the
output on the host."""


def read(ctx):
    return ctx.spans.get("compile")
