"""Faults planted under a serving run through the engine's PUBLIC taps, for the
tests under ``tests/benchmark`` and for ``benchmark/control.py``; no run of the
benchmark plants one.

The seam is the contract PERF.md section 3 lists: ``Engine.submit_request``
(temperature, top_p, ``capture_logits``) -> ``Request.generated`` /
``Request.capture_logits``.  No attribute of ``galvatron_tpu.serving.engine``
that starts with ``_`` is named, so a sampler that leaves the host keeps these
faults working.

- ``submitting(change)``: every request is submitted with the keyword
  arguments ``change`` returns for it (a sampler at the wrong temperature, one
  that ignores ``top_p``): the engine draws from another distribution than the
  request stated, which is what the runner's records hold.
- ``last_token(pick)``: the LAST token of a request whose rows are kept is
  altered where the engine hands it over (``generated.append``, after the draw
  and after the tap wrote the row it was drawn from).  The last token feeds no
  later step, so every logits row stays the reference's.
"""

from __future__ import annotations

import contextlib
from typing import Any, Callable, Dict, Optional

import numpy as np


class LastTokenAltered(list):
    """A request's ``generated`` list whose ``append`` alters the request's last
    token: ``pick(row, token, request)`` returns the token to hand over, ``row``
    the logits row the tap has just written for it."""

    def __init__(self, early, req, pick):
        super().__init__(early)
        self._req, self._pick = req, pick

    def append(self, tok) -> None:
        req = self._req
        if len(self) == req.max_new_tokens - 1 and req.capture_logits is not None:
            tok = int(self._pick(np.asarray(req.capture_logits[len(self)]), int(tok), req))
        super().append(tok)


@contextlib.contextmanager
def submitting(change: Optional[Callable[[Dict[str, Any]], Dict[str, Any]]] = None,
               after: Optional[Callable[[Any], None]] = None):
    """While open, ``Engine.submit_request`` passes its keyword arguments through
    ``change`` and hands the ``Request`` it returns to ``after``."""
    from galvatron_tpu.serving import Engine

    real = Engine.submit_request

    def submit_request(self, tokens, max_new_tokens, *args, **kw):
        req = real(self, tokens, max_new_tokens, *args, **(change(kw) if change else kw))
        if after:
            after(req)
        return req

    Engine.submit_request = submit_request
    try:
        yield
    finally:
        Engine.submit_request = real


def last_token(pick: Callable[[np.ndarray, int, Any], int]):
    """``submitting`` with every kept request's last token altered by ``pick``."""
    def swap(req):
        if req.capture_logits is not None:
            req.generated = LastTokenAltered(req.generated, req, pick)

    return submitting(after=swap)


def runner_up_if_greedy(row: np.ndarray, tok: int, req) -> int:
    """A greedy request's token becomes the runner-up of its row (the largest
    logit strictly under the best: tokens that tie share the best)."""
    if req.temperature >= 1e-3:
        return tok
    return int(np.flatnonzero(row == row[row < row.max()].max())[0])


def outside_nucleus_if_sampled(row: np.ndarray, tok: int, req) -> int:
    """A sampled request's token becomes the id with the smallest logit of its
    row: outside any nucleus under 1."""
    return tok if req.temperature < 1e-3 else int(np.argmin(row))


def hot(kw: Dict[str, Any], temperature: float = 1.0) -> Dict[str, Any]:
    """A sampler at ``temperature`` where a sampled request states another."""
    return dict(kw, temperature=temperature) if kw.get("temperature", 0.0) >= 1e-3 else kw


def no_nucleus(kw: Dict[str, Any]) -> Dict[str, Any]:
    """A sampler that ignores ``top_p`` for the sampled requests."""
    return dict(kw, top_p=0.0) if kw.get("temperature", 0.0) >= 1e-3 else kw
