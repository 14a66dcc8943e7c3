"""Programs that reached the backend between window open and close:
jax.monitoring compile events, which a persistent-cache hit fires too.
Anything but 0 means a shape was not warmed up in set-up.  One reader for
`window_compiles.train` and `window_compiles.serve` (the manifest splits the
quantity by the end-to-end metric it moves)."""


def read(ctx):
    return ctx.records.get("window_compiles")
