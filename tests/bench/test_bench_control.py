"""The control: the program's own lower-precision path (int8 power-of-two
weights, ``quantized``), put in the served path of the tiny cell, has to come
out not correct.  On the chip, at the cells' own sizes, ``bench/calibrate.py``
takes the same readings; the limits in ``bench/cells/`` sit between them."""
import json

import tiny
from bench import calibrate, run


def test_int8_control_is_not_correct(tmp_path, capsys):
    root = tiny.make_tree(tmp_path)
    run.main(["--workload", "tiny-qwen2.chat", "--seed", "4",
              "--seconds", "4", "--trace", "0"], require_tpu=False, root=root,
             engine=calibrate.CONTROL)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is False
    gap = line["checks"]["mean_logit_gap"]
    assert gap["value"] > gap["limit"]
