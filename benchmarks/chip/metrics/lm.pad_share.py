"""LM serving (``ExplanationServer`` token launches): the share of the
prompt positions the device computed that were padding, left padding to
the pow2 length bucket and padding rows alike.  Read from the token-explain
launch spans' ``tokens_live`` and ``tokens_padded`` arguments; a program
whose spans carry no such counts leaves nothing to read."""


def read(ctx):
    live = padded = 0
    for s in ctx.spans:
        if s.name == "engine.attribute" and "tokens_padded" in s.args:
            live += s.args["tokens_live"]
            padded += s.args["tokens_padded"]
    return None if padded <= 0 else 1.0 - live / padded
