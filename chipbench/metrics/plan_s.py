"""Host seconds of slice search, slicing, DAG, DSH schedule, plan and deep
validation (the entry's ``plan`` span); nothing where the entry plans
nothing."""


def read(ctx):
    return ctx.spans.get("plan")
