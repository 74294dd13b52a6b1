"""A counter, or a ratio of products of counters, times ``scale``."""


def _product(counters, names):
    out = 1.0
    for n in names:
        if n not in counters:
            return None
        out *= counters[n]
    return out


def reduce(ctx, numerator, denominator=(), scale: float = 1.0):
    num = _product(ctx.counters, numerator)
    den = _product(ctx.counters, denominator)
    if num is None or den is None or den == 0:
        return None
    return scale * num / den
