"""Train step: host clock around the K dispatched local steps of a round,
ending in block_until_ready, divided by K; mean over the window's rounds."""


def read(facts):
    if facts.get("kind") != "fedround" or not facts.get("train_s"):
        return None
    return 1e3 * sum(facts["train_s"]) / (len(facts["train_s"])
                                          * facts["local_steps"])
