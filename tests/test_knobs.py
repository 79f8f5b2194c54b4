"""paddle_tpu/knobs.py: a tuning knob is the validated environment value, else
the caller's default; garbage is a ValueError that names the variable."""

import pytest

from paddle_tpu import knobs
from paddle_tpu.serving.kv_cache import page_size_from_env

DEFAULT = 48

# variable -> the reader's value with `DEFAULT` as the caller's default
READ = {
    # through the serving tier's entry, which is what the engine calls
    "PADDLE_TPU_PAGE_SIZE": lambda: page_size_from_env(DEFAULT),
    "PADDLE_TPU_SPEC_K": lambda: knobs.speculation_k(DEFAULT),
    "PADDLE_TPU_STEPS_PER_DISPATCH":
        lambda: knobs.steps_per_dispatch(DEFAULT),
    "PADDLE_TPU_SPEC_DRAFT_LAYERS":
        lambda: knobs.spec_draft_layers(DEFAULT),
}

# case -> (the values the variable takes in turn, None for unset; what the
# reader then gives: a value, or the words of its ValueError)
CASES = {
    "unset": ((None,), DEFAULT),
    "valid": (("32",), 32),
    "not_an_integer": (("x32", "3.5"), "is not an integer"),
    "not_positive": (("0", "-32"), "must be a positive integer"),
}
PARAMS = [pytest.param(var, *CASES[case], id=f"{var}-{case}")
          for var in READ for case in CASES]
PARAMS.append(pytest.param("PADDLE_TPU_PAGE_SIZE", ("24",), "multiple of 16",
                           id="PADDLE_TPU_PAGE_SIZE-not_whole_tiles"))


@pytest.mark.parametrize("var,raws,want", PARAMS)
def test_knob_from_the_environment(var, raws, want, monkeypatch):
    for other in READ:
        monkeypatch.delenv(other, raising=False)
    for raw in raws:
        if raw is not None:
            monkeypatch.setenv(var, raw)
        if isinstance(want, int):
            assert READ[var]() == want
            continue
        with pytest.raises(ValueError, match=want) as err:
            READ[var]()
        assert var in str(err.value)
