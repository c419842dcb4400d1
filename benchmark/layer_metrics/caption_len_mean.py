"""Mean length of the captions the cell's traffic is made of (sampled lanes
of the policy for ``cst``, reference rows for ``xe``), tokens before EOS. It
says the policy or corpus is still the one the cell was defined on."""


def read(reading):
    return reading["result"].get("caption_len_mean")
