"""Tokens delivered over the token slots the decode steps offered:
delta(tokens_emitted) / (decode steps x batch)."""


def read(cap):
    steps = cap.decode_steps()
    toks = cap.stats1.get("tokens_emitted", 0) - cap.stats0.get(
        "tokens_emitted", 0)
    if not steps or not toks:
        return None
    return 100.0 * toks / (steps * cap.batch)
