"""Process start to the first measured step: native library check, owners up
and shards registered, JAX up, compile or cache read, reference checks,
warm-up."""


def read(ctx):
    return ctx["setup_s"]
