"""The comparison that decides ``correct`` tells a broken timed path from
a sound one.  At a size a CPU test holds: the float8 control, judged in
the program's place, comes out not correct where the program stays under
the limit, and a run whose engine is broken underneath after warm-up
comes out not correct."""
import pytest

from chipbench_helpers import new_cell_root, run_on_cpu


def test_the_float8_control_reads_above_the_limit(tmp_path):
    # small-olmo on the CPU: the program read at most 0.0038 over four
    # seeds, the control at least 0.11; the limit is 0.03
    root = new_cell_root(tmp_path, config="small-olmo", traffic="small-chat")
    out = run_on_cpu(root, "small-olmo.small-chat", 2**31 + 17, 4.0, control=True)
    limit = out["checks"]["logit_gap_max"]["limit"]
    assert out["program_logit_gap_max"] <= limit
    assert out["checks"]["logit_gap_max"]["value"] > limit
    assert out["failed"] >= 1
    assert out["correct"] is False


def _alter_tokens(engine):
    """Every token the decode block writes comes out one id higher."""
    block, vocab = engine._decode_block, engine.cfg.vocab

    def altered(params, cache, state, n_rounds):
        cache, state = block(params, cache, state, n_rounds)
        return cache, dict(state, out_buf=(state["out_buf"] + 1) % vocab)

    engine._decode_block = altered


def _keep_state(engine):
    """The decode block returns its state unchanged."""
    engine._decode_block = lambda params, cache, state, n_rounds: (cache, state)


@pytest.mark.parametrize("fault", [_alter_tokens, _keep_state])
def test_a_broken_timed_path_is_not_correct(tmp_path, fault):
    root = new_cell_root(tmp_path)
    out = run_on_cpu(root, "tiny-olmo.tiny-chat", 2**31 + 19, 2.0, corrupt=fault)
    assert out["correct"] is False
