"""Lock polls of waiting threads (each a yield or a park in PBComb's
``_wait_while``) per operation completed in the window, from the
program's ``waiter_polls`` counter."""


def read(obs):
    d = obs["delta"]
    if "waiter_polls" not in d or not obs["n_ops"]:
        return None
    return d["waiter_polls"] / obs["n_ops"]
