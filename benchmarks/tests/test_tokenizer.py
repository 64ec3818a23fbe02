import pytest

from benchmarks import tokenizer as tk


@pytest.mark.parametrize("vocab", [512, 32000, 51200])
def test_every_id_is_one_nonempty_piece_and_comes_back(vocab):
    tok = tk.PieceTokenizer(vocab)
    ids = list(range(vocab))
    text = tok.decode(ids)
    assert len(text) == tk.PIECE * vocab
    assert all(len(tk.piece(i)) == tk.PIECE for i in (0, 1, vocab - 1))
    assert tok.encode(text) == ids
    # decoding is concatenative, which the provider's incremental decode needs
    assert tok.decode(ids[:100]) + tok.decode(ids[100:200]) == tok.decode(ids[:200])
    assert "<" not in text and "�" not in text


def test_template_counts_and_prefix_property():
    tok = tk.PieceTokenizer(32000)
    sys_ids, task, reply, tool = [9, 10, 11], [20, 21], [5, 2, 3], [40]
    turn1 = [("system", sys_ids), ("user", task)]
    ids1 = tk.template_ids(turn1)
    assert len(ids1) == 5 + tk.template_overhead(2)
    msgs = [{"role": r, "content": tk.text_of(c)} for r, c in turn1]
    assert tok.apply_chat_template(msgs) == ids1
    # the next turn's prompt starts with this turn's prompt and its reply
    ids2 = tk.template_ids(turn1 + [("assistant", reply), ("user", tool)])
    assert ids2[:len(ids1) + len(reply)] == ids1 + reply
