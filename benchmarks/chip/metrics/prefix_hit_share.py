"""Share of the window's admitted prompt tokens served from the
prefix cache (delta of prefix_hit_tokens over delta of prompt_tokens),
in percent."""


def read(rec):
    s = rec["stats"]
    if s["prompt_tokens"] <= 0:
        return None
    return 100.0 * s["prefix_hit_tokens"] / s["prompt_tokens"]
