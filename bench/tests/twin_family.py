"""A family for the tests alone: the dense block under another name, its
leaves made in the reverse order (so its weights differ from ``dense``'s
for a seed), and every hook counted.  A configuration reaches it by its
``"family"`` key only, which shows that a new block needs no file of the
harness changed."""

from collections import Counter

from bench.reference import dense

CALLS: Counter = Counter()

HOOKS = ("empty_tree", "check_arch", "matmul_params", "attention_shape",
         "attention_flops", "train_readings", "served_gap_fn",
         "int8_weights", "control_pick_fn")


def _counted(name):
    fn = getattr(dense, name)

    def call(*args, **kwargs):
        CALLS[name] += 1
        return fn(*args, **kwargs)
    return call


for _name in HOOKS:
    globals()[_name] = _counted(_name)


def leaf_specs(config: dict):
    CALLS["leaf_specs"] += 1
    return dense.leaf_specs(config)[::-1]
