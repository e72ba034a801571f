"""Programs XLA BUILT before the window and wrote to the persistent cache
(`COMPILES.cache_misses` in the snapshot at the window's start; jax records
the event at the write, so the tiny eager programs under the cache's
thresholds never count). 0 says the run's `setup_s` is a warm one; anything
else says how many of the cell's programs it had to build."""


def read(ctx):
    return ctx["compiles_before"].get("cache_misses")
