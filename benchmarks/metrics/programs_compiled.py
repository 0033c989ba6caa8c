"""Programs this process compiled and wrote to the persistent cache
(``counters()["compile_cache"]["misses"]``; nothing compiles after set-up):
0 on a warm cache, where ``compile_s`` is the cache's reads. The hits and
the step's backend seconds are printed beside it."""

from ddbench import passes


def read(ctx):
    cache = passes.counter(ctx, "compile_cache")
    if cache is None:
        return None
    step = (passes.counter(ctx, "compile_s") or {}).get(
        "ddstore_lm_train_step", {})
    print(f"programs_compiled: {cache}; ddstore_lm_train_step "
          + ", ".join(f"{k} {v:.2f}" for k, v in step.items()), flush=True)
    return float(cache["misses"])
