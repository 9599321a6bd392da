"""Each client draws its ops in blocks: every block holds each op its
given number of times, in an order drawn from the seed.  ``{"kind":
"shuffled", "ops": [["insert", 8], ["delete_min", 8]]}`` is a random
50/50 mix in which no client is ever more than 8 ops off balance."""


def names(spec):
    return {op for op, _ in spec["ops"]}


def ops(spec, rng):
    block = [op for op, count in spec["ops"] for _ in range(count)]
    while True:
        rng.shuffle(block)
        yield from block
