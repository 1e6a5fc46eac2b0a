"""Share of the query-key pairs the windowed kernels fold that the window
keeps: the pairs a causal sliding window keeps in one layer and query
head (the driver's ``swa_kept_pairs``), over the pairs the windowed
forward's tile plan folds for that layer and head (the program's
``attention_band_pairs`` counter over its ``attention_window`` counter,
their rise at set-up) (%).  None where the program counts no windowed
tile plan."""


def read(ctx):
    band = ctx.units.get("band_pairs")
    if not band:
        return None
    return 100.0 * ctx.units["swa_kept_pairs"] / band
