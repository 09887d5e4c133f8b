"""Data products per optimizer pass: sum over the window's fits of
(``gather_products`` + ``transpose_products``) / 2 — pairs of one ``X v`` and
one ``X^T d``, what ``flops_bytes.pass_flops`` prices — over the sum of their
passes. Read from the program's own fit records
(``TrainingMetrics.fit_records``: the optimizers count the products inside
their loops); nothing where the program keeps no such record."""


def window_fits(run):
    """The records of the window's fits, in order: the last
    ``len(pieces)`` that ``fit_distributed`` left. ``None`` where the
    program has no fit records, or fewer than the window's pieces."""
    try:
        from photon_ml_tpu.obs.metrics import training_metrics

        records = training_metrics().fit_records()
    except (ImportError, AttributeError):
        return None
    n = len(run.window["pieces"])
    return records[-n:] if 0 < n <= len(records) else None


def product_pairs(record):
    g, t = record.get("gather_products"), record.get("transpose_products")
    return None if g is None or t is None else (g + t) / 2


def read(run):
    fits = window_fits(run)
    if not fits:
        return None
    pairs = [product_pairs(r) for r in fits]
    passes = sum(r["iterations"] or 0 for r in fits)
    if any(p is None for p in pairs) or passes <= 0:
        return None
    return sum(pairs) / passes
