"""Serving engine, closed-loop cells: the window's wall time per decode
iteration (``stats()["steps"]`` delta), with everything that rides between
two iterations (admission, prefill chunks, sampling on the host)."""


def read(facts):
    if facts.get("kind") != "closed_loop" or not facts.get("steps"):
        return None
    return 1e3 * facts["window_s"] / facts["steps"]
