"""Aggregation: what federation adds to plain training, per round: the
round's time minus the time of its K local steps; mean over the window."""


def read(facts):
    if facts.get("kind") != "fedround" or not facts.get("round_s"):
        return None
    extra = [r - t for r, t in zip(facts["round_s"], facts["train_s"])]
    return 1e3 * sum(extra) / len(extra)
