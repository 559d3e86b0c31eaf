"""Faults planted in the timed path, each of which the comparison that
decides ``correct`` has to catch.  Each takes a ``setattr`` (a test's
``monkeypatch.setattr``, or the builtin in a process of its own) and has to
be planted before the scheduler is built, since the program's jitted
functions read what they call when they are traced.

* ``altered_token``: a token altered where it is produced, the decode
  chunk's sampler;
* ``state_unchanged``: a decode step that returns its state unchanged, the
  K/V pool as it came in, so later steps never see the keys of the tokens
  before them.

The exchange between chips does not exist on one chip, and no step of a
serving cell takes a mean over a batch: those faults do not apply here."""


def altered_token(patch) -> None:
    import repro.serve.scheduler as S

    sample = S._sample_rows

    def wrong(logits, temperature, keys):
        return (sample(logits, temperature, keys) + 1) % logits.shape[-1]

    patch(S, "_sample_rows", wrong)


def state_unchanged(patch) -> None:
    import repro.serve.scheduler as S

    step = S.T.decode_step

    def frozen(cfg, params, cache, *args, **kw):
        logits, _ = step(cfg, params, cache, *args, **kw)
        return logits, cache

    patch(S.T, "decode_step", frozen)


FAULTS = {"altered_token": altered_token, "state_unchanged": state_unchanged}
