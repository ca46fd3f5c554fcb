"""The cells at sizes a CPU holds: the same files, the sizes cut."""
import copy
import json
import os

from lib import spec

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(kind, name):
    with open(os.path.join(HERE, kind, name + ".json")) as f:
        return json.load(f)


def gpt(batch=8, seq=128, width=128, vocab=2000):
    """Large enough that the float8 control stands clear of bfloat16 on
    the worst leaf, as it does at the cell's size."""
    cfg = _load("configs", "gpt2-medium")
    cfg.update(n_layer=2, n_embd=width, n_head=4, n_positions=seq,
               n_inner=4 * width, vocab_size=vocab)
    cfg["symbol"]["kwargs"] = dict(seq_len=seq, num_hidden=width,
                                   num_heads=4, num_layers=2,
                                   vocab_size=vocab)
    cfg["input"] = {"kind": "tokens", "seq_len": seq, "vocab": vocab}
    tr = _load("traffic", "tokens_b8")
    tr.update(batch=batch, samples_per_row=seq, reference_row_block=2)
    return _cell("tiny_gpt", cfg, tr, "gpt2m_train", "train_tokens_per_s",
                 "tokens/s")


def resnet(batch=4, image=96):
    cfg = _load("configs", "resnet50")
    cfg["image_size"] = image
    cfg["input"]["shape"] = [image, image, 3]
    tr = _load("traffic", "images_b256")
    tr.update(batch=batch)
    return _cell("tiny_resnet", cfg, tr, "resnet50_train",
                 "train_img_per_s", "img/s")


def _cell(name, cfg, tr, limits_of, rate, unit):
    """The limits are those of the cell ``limits_of``: the same numbers
    are compared, each against 1 until :func:`with_limits` says
    otherwise (a cell's own limits are chip readings at its own size)."""
    limits = _load("limits", limits_of)
    limits["limits"] = {k: 1.0 for k in limits["limits"]}
    cell = spec.Cell.of(name, cfg, tr, limits)
    cell.end_to_end = [{"name": rate, "unit": unit},
                       {"name": "setup_s", "unit": "s"}]
    return cell


def with_limits(cell, limits):
    cell = copy.copy(cell)
    cell.limits = dict(cell.limits, limits=dict(limits))
    return cell
