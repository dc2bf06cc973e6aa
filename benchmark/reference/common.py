"""What the frozen reference needs from the program's utilities, copied
so that the reference imports nothing of the system under test."""

ROOT_ID = '00000000-0000-0000-0000-000000000000'


class AutomergeError(Exception):
    pass


class RangeError(AutomergeError, ValueError):
    """Mirrors JS RangeError (invalid value / out of range)."""


def less_or_equal(clock1, clock2):
    """True if every component of vector clock `clock1` is <= the matching
    component of `clock2` (upstream `src/common.js:14-18`)."""
    for key in set(clock1) | set(clock2):
        if clock1.get(key, 0) > clock2.get(key, 0):
            return False
    return True
