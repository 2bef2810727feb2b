"""What a model hands the step beside its logits, declared where it is sown.

A layer `self.sow("intermediates", name, value)`s what it counted or what
it asks of the loss; the file that sows says, next to the sow, what the
value is, by registering one function of the collection:

    @counters     intermediates -> {name: scalar}, {} where the model
                  sowed none of it: they ride in the step's metrics
    @term         (intermediates, batch, ce) -> None | (term, {name:
                  scalar}): `term` joins the loss, the scalars the metrics
    @param_steps  intermediates -> a tree over part of `params`: steps on
                  variables the optimizer leaves alone ({} for none)
    @objective    (intermediates, batch, logits) -> None | scalar: the
                  OBJECTIVE itself, where a model sowed its own targets
                  and weights — the scalar that stands where the
                  next-token `cross_entropy_loss(logits, batch["labels"])`
                  stands, `collect`'s `ce`.  The contract: None for a
                  model that sowed none of it (every registrant sees every
                  model's collection); float32 statistics over the
                  logits' own dtype and a VJP that holds no (b, T, V)
                  float32 array, as the cross-entropy's; at most ONE
                  registrant answers a model (`objective_of` refuses
                  two); terms and counters join it as they join the
                  cross-entropy

and `collect` is the one place the step asks (`trainer/train_step.
make_lm_loss`, which asks `objective_of` first; `parallel/pipeline.py`
for what a pipelined block adds).
Registration happens because the module that sows is imported by whoever
built the model — a value cannot be sown by a module that was never
imported — so this file imports no model file and `collect` needs no
list.  A function is kept under its qualified name: importing or
reloading its module again registers it once.

Parity: none — the reference's models return a loss of their own.
"""

from __future__ import annotations

import jax

_COUNTERS, _TERMS, _STEPS, _OBJECTIVES = {}, {}, {}, {}  # by qualified name
# float32 addition is not associative and the pinned losses read the last
# bit: ((ce + the MoE layers' aux) + the indexers' KL) + the second
# prediction's, whatever order the models' files were imported in; a term
# not named here follows these, by qualified name
_TERM_ORDER = ("moe_aux_term", "collect_attention_aux_loss",
               "collect_mtp_loss")


def _register(table: dict, fn):
    table[f"{fn.__module__}.{fn.__qualname__}"] = fn
    return fn


def counters(fn):
    return _register(_COUNTERS, fn)


def term(fn):
    return _register(_TERMS, fn)


def param_steps(fn):
    return _register(_STEPS, fn)


def objective(fn):
    return _register(_OBJECTIVES, fn)


def sown(intermediates, name: str):
    """The leaves sown under `name`, whatever module path they sit on."""
    for path, leaf in jax.tree_util.tree_flatten_with_path(intermediates)[0]:
        if name in [getattr(p, "key", getattr(p, "name", None))
                    for p in path]:
            yield leaf


def _merge(into: dict, more: dict) -> None:
    """Lay `more` over `into`, level by level; a name two registrants
    both give is a fault of theirs, said at trace time."""
    for key, value in more.items():
        if isinstance(value, dict) and isinstance(into.get(key), dict):
            _merge(into[key], value)
        elif key in into:
            raise ValueError(f"two registrants of models/sown.py give {key!r}")
        else:
            into[key] = value


def objective_of(intermediates, batch, logits):
    """The registered objective's scalar for one forward pass, or None:
    the step then takes the next-token cross-entropy, as it always did."""
    given = [(key, out) for key in sorted(_OBJECTIVES)
             if (out := _OBJECTIVES[key](intermediates, batch, logits))
             is not None]
    if len(given) > 1:
        raise ValueError(f"two objectives of models/sown.py answer one "
                         f"model: {[key for key, _ in given]}")
    return given[0][1] if given else None


def collect(intermediates, batch, ce):
    """(loss, stats) of one forward pass: `ce` plus every registered term
    in `_TERM_ORDER`, and every registered reducer's counters with the
    terms' own beside them; `stats["param_steps"]` where a layer asked for
    a step."""
    def place(key):
        name = _TERMS[key].__name__
        return (_TERM_ORDER.index(name) if name in _TERM_ORDER
                else len(_TERM_ORDER), key)

    loss, stats = ce, {}
    for key in sorted(_TERMS, key=place):
        out = _TERMS[key](intermediates, batch, ce)
        if out is not None:
            loss = loss + out[0]
            _merge(stats, out[1])
    for key in sorted(_COUNTERS):
        _merge(stats, _COUNTERS[key](intermediates))
    steps: dict = {}
    for key in sorted(_STEPS):
        _merge(steps, _STEPS[key](intermediates))
    if steps:
        stats["param_steps"] = steps
    return loss, stats
