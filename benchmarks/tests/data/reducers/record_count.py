"""A reducer added as a file only (tests): how many step records the
window gave."""


def reduce(spec, layer, run):
    return float(len(layer.get("records", ())))
