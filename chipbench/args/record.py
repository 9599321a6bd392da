"""A RECORD's ``(session, seq, response)``: the session uniform over
``sessions``, the seq and the response each uniform in its ``[lo, hi)``:
``{"kind": "record", "sessions": 65536, "seq": [8589934592,
4611686018427387904], "response": [-4611686018427387904,
4611686018427387904]}``."""


def draw(spec, rng):
    return (rng.randrange(spec["sessions"]), rng.randrange(*spec["seq"]),
            rng.randrange(*spec["response"]))
