"""Each client repeats the same run of ops, in order: ``{"kind":
"cycle", "ops": [["insert", 1], ["delete_min", 1]]}`` alternates one
insert and one delete_min."""


def names(spec):
    return {op for op, _ in spec["ops"]}


def ops(spec, rng):
    while True:
        for op, count in spec["ops"]:
            for _ in range(count):
                yield op
