"""Megabytes (1e6 bytes) sent from the host to the device per answered
query: the ``bytes`` of every ``device.upload`` span (a model's
statistic put in the device cache, or a volatile gap's passing
through it) and the ``bytes_in`` of every ``train.fit`` span (a gap's
doc-term matrix), over the answered ``serve.query`` spans.  Nothing
where gaps were trained but no ``train.fit`` span says what they
uploaded."""


def read(ctx):
    names = {s.name for s in ctx.spans}
    if "train" in names and "train.fit" not in names:
        return None
    answered = sum(1 for s in ctx.spans if s.name == "serve.query"
                   and not s.attrs.get("error")
                   and "outcome" not in s.attrs)
    if not answered:
        return None
    sent = sum(float(s.attrs.get("bytes", 0)) for s in ctx.spans
               if s.name == "device.upload")
    sent += sum(float(s.attrs.get("bytes_in", 0)) for s in ctx.spans
                if s.name == "train.fit")
    return sent / 1e6 / answered
