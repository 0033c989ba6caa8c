"""Owner spawn to the last shard registered: every rank builds its shard from
the seed, joins the group and adds its variables."""


def read(ctx):
    return ctx["store_up_s"]
