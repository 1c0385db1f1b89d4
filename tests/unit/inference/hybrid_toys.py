"""What the serving suites of toy models share (the hybrid models' six: the
newest, ``test_lfm2_serving.py``, a toy whose state kind keeps a convolution
tail and no state, ``lfm2_moe_config("tiny")``; and
``test_run_ahead.py`` and ``test_fleet.py``, whose every test builds servers of
one toy model): a model's ``init`` and its ``apply`` as ONE jitted program each,
and the pair of fixtures that keeps a file's compiled programs until the
file's last test.

A test file takes the fixtures by importing them
(``from tests.unit.inference.hybrid_toys import _clear_jax_caches,
_compiled_programs_live_as_long_as_the_file``): pytest then finds them in that
module and the first stands in for ``conftest.py``'s fixture of the same name
there, and nowhere else.
"""

import jax
import numpy as np
import pytest


def seeded(lm):
    """The model's own ``init`` from key 0 as ONE jitted program: leaf by leaf
    the interpreter compiles a kernel for every draw, scale and ``ones`` of
    every shape (12-19 s a toy model, most of such a file's set-up)."""
    return jax.jit(lambda key: lm.init(key, None))(jax.random.PRNGKey(0))


def apply_logits(lm, params, tokens):
    """``lm.apply`` as one jitted program (traced now: a stand-in patched into
    ``hybrid_moe`` is what it calls)."""
    return np.asarray(jax.jit(lambda p, t: lm.apply(p, t))(params, tokens))


@pytest.fixture(autouse=True, scope="module")
def _compiled_programs_live_as_long_as_the_file():
    """``conftest.py`` drops every compiled program after EVERY test, which
    made each test of such a file compile its model's two widths again (the
    drivers of one model share one jitted forward, ``_FORWARDS``, and the
    engines one program cache): the file's programs are a few toy models' on
    one device, kept until the file's last test and dropped then."""
    yield
    jax.clear_caches()


@pytest.fixture(autouse=True)
def _clear_jax_caches():
    """In place of ``conftest.py``'s: nothing after a test."""
    yield
