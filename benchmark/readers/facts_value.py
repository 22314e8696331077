"""A count the program keeps, as the driver hands it on in its facts
under the spec's ``fact``.  A program without it gives nothing to
read."""


def read(red, facts, peaks, spec):
    value = facts.get(spec["fact"])
    return None if value is None else float(value)
