"""The operations and bytes of served requests, pinned for the qwen3-8b
configuration's shapes: `counts.served_request` takes a forward call's
counts from the configuration's model family (``reference/dense_lm.py``)
and gives the numbers it gave when it counted the dense decoder itself."""
import pytest

import counts
import loader

#: (n_prompt, n_hit, n_gen) -> (operations, bytes)
PINNED = {
    (512, 0, 4): (811412881408, 13987530752),
    (600, 580, 4): (44199313408, 13990709248),
    (130, 0, 1): (205254950912, 5581828096),
    (990, 900, 4): (157149626368, 14023231488),
    (40, 40, 4): (11163795456, 11155775488),
}


@pytest.mark.parametrize("shape", sorted(PINNED))
def test_served_request_pinned(shape):
    model = loader.load_json(loader.BENCH / "configs" / "qwen3-8b-x2.json")
    assert counts.served_request(model, *shape) == PINNED[shape]


def test_call_counts_from_the_family():
    model = loader.load_json(loader.BENCH / "configs" / "qwen3-8b-x2.json")
    call = loader.reference(model["engine"]["family"]).call_counts
    # a 16-token extend after 300 cached tokens, logits for 3 positions
    assert call(model, 16, 300, 3) == (28753526784, 2793547776)
    tiny = loader.load_json(loader.BENCH / "checks" / "fixtures"
                            / "tiny-x2.json")
    assert counts.served_request(tiny, 21, 5, 6) == (3888640, 1542144)
