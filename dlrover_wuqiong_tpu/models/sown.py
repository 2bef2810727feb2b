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

and `collect` is the one place the step asks (`trainer/train_step.
make_lm_loss`; `parallel/pipeline.py` for what a pipelined block adds).
Registration happens because the module that sows is imported by whoever
built the model — a value cannot be sown by a module that was never
imported — so this file imports no model file and `collect` needs no
list.  A function is kept under its qualified name: importing or
reloading its module again registers it once.

Parity: none — the reference's models return a loss of their own.
"""

from __future__ import annotations

import jax

_COUNTERS, _TERMS, _STEPS = {}, {}, {}  # qualified name -> function
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
