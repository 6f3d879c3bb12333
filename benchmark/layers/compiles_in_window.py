"""Programs compiled or loaded from the persistent cache inside the window
(``compilation_cache_stats()`` hits + misses, end less start).  Has to
read 0: every shape is warmed in set-up."""


def read(ctx):
    return float(ctx.compiles_in_window)
