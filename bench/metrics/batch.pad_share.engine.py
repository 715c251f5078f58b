"""Share of dispatched predictor rows that are padding, in percent."""


def read(r):
    rows = sum(float(labels["shape"]) * n for labels, n in
               r.cells("capsim_predictor_batches_total"))
    if not rows:
        return None
    return 100.0 * r.counter("capsim_predictor_pad_rows_total") / rows
