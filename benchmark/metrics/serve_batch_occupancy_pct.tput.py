"""Mean number of sequences that decoded in a tick of the window over
`max_batch` (`serve_tokens_total{kind="decode"}` a tick of
`serve_engine_steps_total`; no speculative decoding, so a token a sequence).
The slots that do not decode are waiting for their prompt's chunks.

The reader of the longdoc cell (moves serve_tokens_per_s)."""


def read(obs):
    ticks = obs.get("ticks")
    if not ticks:
        return None
    mean = sum(t[2] for t in ticks) / len(ticks)
    return 100.0 * mean / obs["traffic"]["engine"]["max_batch"]
