"""Serving engine, open-loop cells: the share of decode-row slots that
produced a token, over the window's decode iterations: tokens the sink saw
other than first tokens (those come from prefill) / (steps x slots)."""


def read(facts):
    if facts.get("kind") != "open_loop" or not facts.get("steps"):
        return None
    decoded = facts["pushed_tokens"] - facts["first_tokens"]
    return 100.0 * decoded / (facts["steps"] * facts["slots"])
