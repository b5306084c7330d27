"""Wire + placement on arrival: from the round's start (the peer's tree is
requested) to the tree being a resident jax.Array on the lead's mesh;
median over the window's rounds. Overlaps the local steps."""


def read(facts):
    xs = sorted(facts.get("push_place_s") or [])
    if facts.get("kind") != "fedround" or not xs:
        return None
    return 1e3 * xs[len(xs) // 2]
