"""An integer argument drawn uniformly from ``[lo, hi)``: ``{"kind":
"uniform", "lo": -4611686018427387904, "hi": 4611686018427387904}``."""


def draw(spec, rng):
    return rng.randrange(spec["lo"], spec["hi"])
