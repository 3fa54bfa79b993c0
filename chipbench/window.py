"""Window arithmetic over the handler's `EndIteration` stamps. The window
runs from its first stamp to its last; a step belongs to it when it ended
after the first stamp and no later than the last."""


def gaps_ms(stamps):
    return [(b - a) * 1e3 for a, b in zip(stamps, stamps[1:])]


def rate_per_s(stamps, samples_per_step):
    """All samples of all steps that ended in the window, over the whole
    window: not a mean or median of per-step rates."""
    if len(stamps) < 2:
        raise ValueError("a window needs two stamps, got %d" % len(stamps))
    return (len(stamps) - 1) * samples_per_step / (stamps[-1] - stamps[0])


def percentile(values, q):
    """The q-th percentile (0-100) by linear interpolation between order
    statistics, over all the values."""
    if not values:
        raise ValueError("percentile of nothing")
    ordered = sorted(values)
    pos = (len(ordered) - 1) * q / 100.0
    low = int(pos)
    high = min(low + 1, len(ordered) - 1)
    return ordered[low] + (ordered[high] - ordered[low]) * (pos - low)
