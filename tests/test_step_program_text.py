"""The step programs' lowered text at ``tiny`` is pinned, family by family.

A PR that moves host code (the scheduler's loop, delivery, admission's
bookkeeping) must leave every device program as it was: the same
``multi``, ``ragged`` and ``chunk`` text for every family, so that what
the chip runs, and every number read off it, is the parent's (PERF.md
section 6, "PR 24-31": compare step programs' lowered text at ``tiny``
with the parent's). The digests below were taken from an unpacked ``git
archive`` of the parent of PR 42 (75c4638) with this file's own
``digests()``; PR 42's tree gives the same twelve, and PR 43's (which
moved the mixer both hybrid families run into ``models/mamba2.py`` and
gave ``ops/moe.py`` a second gate) the same twelve again beside the three
of the family it added. PR 44 changed the recurrence's decode kernel
(``ops/pallas/ssd_step.py``: a block of heads a loop turn, the next
block's columns picked a turn ahead) and re-pinned the four programs that
hold it, ``multi`` and ``ragged`` of ``tiny-falcon-h1`` and
``tiny-granite-h``; the other eleven are the parent's.

A PR that changes a device program on purpose re-pins the programs it
meant to change (``python tests/test_step_program_text.py`` prints the
table) and says in PERF.md which moved: a digest that moves without that
is a device change nobody asked for. The text carries no source
locations, so moved lines alone change nothing.
"""

from __future__ import annotations

import hashlib

import pytest

from tests.test_device_names import _programs

FAMILIES = {
    "tiny": {},
    "tiny-sala": {"page_size": 8},
    "tiny-moonlight": {"page_size": 8},
    "tiny-falcon-h1": {"page_size": 8},
    "tiny-granite-h": {"page_size": 8},
}

PINNED = {
    ("tiny", "multi"):
        "8a78e255c9992913c1284d39b05862cb9f6172cbbf4120799573ba9d88024417",
    ("tiny", "ragged"):
        "4eefe54bd9c066bb6db86b78a8e2e865b22ea03b8e07bb3bcfeaaabbfcc0c8a4",
    ("tiny", "chunk"):
        "41e82717727d39a71619c720e683d45901477e7758a1d025e9386f2834c42ebb",
    ("tiny-sala", "multi"):
        "adc28636f4f624b5fe911b8d7ad95e99b1b49bc22f35b31837ec28396af660e0",
    ("tiny-sala", "ragged"):
        "7a8710cbc678236b69cf2fdcaa107c934b44640a91e711ade02b3cc156ddea2d",
    ("tiny-sala", "chunk"):
        "03d671d326053b52aa532ea99e249528f7026625de72cd62f7606f2f8015840a",
    ("tiny-moonlight", "multi"):
        "1e6d874f1c4a643984f888d48966d88c787afc85f75a02e0977579989fd93a83",
    ("tiny-moonlight", "ragged"):
        "bfa5b6fd242705b78f295b375c344dd9626eeb6aff45fe6bc09eb6271204b319",
    ("tiny-moonlight", "chunk"):
        "1397d8933acb4df8b2ab9fe045bb7ffc9e76ee29de18440bbe7e02d723e75179",
    # PR 44: the two mixer families' decode programs hold the new kernel
    ("tiny-falcon-h1", "multi"):
        "febc92afc20b31a8f893578e1cfabcc3a252df56434b6a438d3507dc41d2accc",
    ("tiny-falcon-h1", "ragged"):
        "b3f703788c8795f1b1d1d29d0d503b612f15c54f2e478dfd4f585cf713e95f4f",
    ("tiny-falcon-h1", "chunk"):
        "851381effc8f02a1b81ed1643c9ac1872df62c20c997a4e95c787a10f9f7f8a8",
    # PR 43: a fifth family; its own tree's digests (the parent has none)
    ("tiny-granite-h", "multi"):
        "985f910e95510b35b0f996c3de480e3e46d531da5d27f890552f2fbbf1a7c19b",
    ("tiny-granite-h", "ragged"):
        "3241a0ae50c767d91fc259dbabc033d451effc9d387c0183da3cb1a73890bd53",
    ("tiny-granite-h", "chunk"):
        "317b9fc481451e302bd6f39b859948dcb843f7c1dff14bd53ec96b687a1ade52",
}


def digests(model: str) -> dict[str, str]:
    """sha256 of each step program's lowered text (no debug info)."""
    engine, fns = _programs(model, **FAMILIES[model])
    try:
        return {
            name: hashlib.sha256(
                fn.lower(*args, **kw).as_text().encode()).hexdigest()
            for name, (fn, args, kw) in fns.items()
        }
    finally:
        engine.close()


@pytest.fixture(scope="module")
def lowered():
    memo: dict[str, dict[str, str]] = {}

    def of(model: str) -> dict[str, str]:
        if model not in memo:
            memo[model] = digests(model)
        return memo[model]

    return of


@pytest.mark.parametrize("model, program", sorted(PINNED))
def test_a_step_programs_lowered_text_is_the_parents(lowered, model, program):
    assert lowered(model)[program] == PINNED[model, program], (
        f"{model}: the {program} program's lowered text moved")


if __name__ == "__main__":
    for model in FAMILIES:
        for program, digest in digests(model).items():
            print(f'    ("{model}", "{program}"):\n        "{digest}",')
