"""API-freeze gate over the single-source op schema (reference:
tools/check_api_compatible.py + the api.yaml single-source pattern,
SURVEY.md §2.1#5).

Failing here means the public op surface drifted from
paddle_tpu/ops/op_schema.yaml.  If the change is intentional, regenerate
the schema (python tools/gen_op_schema.py) and commit the diff — that
diff is the reviewable API-change record.
"""
import inspect

import pytest

import paddle_tpu as paddle
import paddle_tpu.ops as ops
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.ops.schema import all_ops, current_signature, get_op_info


def _live_surface():
    seen = {}
    submods = {"creation": ops.creation, "math": ops.math_mod,
               "manipulation": ops.manipulation, "logic": ops.logic,
               "linalg": ops.linalg, "search": ops.search,
               "stat": ops.stat, "random": ops.random}
    # NOT `import paddle_tpu.ops.einsum as einsum_mod`: the package
    # re-exports the einsum FUNCTION under the same name, and `import as`
    # prefers the package attribute over sys.modules — dir() over the
    # function would silently drop the whole submodule from the gate
    import importlib

    submods["einsum"] = importlib.import_module("paddle_tpu.ops.einsum")
    for sub, mod in submods.items():
        for name in dir(mod):
            if name.startswith("_"):
                continue
            fn = getattr(mod, name)
            if not callable(fn) or inspect.isclass(fn):
                continue
            if getattr(fn, "__module__", "").startswith("paddle_tpu.ops"):
                seen.setdefault(name, (sub, fn))
    return seen


class TestOpSchemaGate:
    def test_every_declared_op_exists_with_signature(self):
        live = _live_surface()
        missing, changed = [], []
        for name in all_ops():
            spec = get_op_info(name)
            if name not in live:
                missing.append(name)
                continue
            _, fn = live[name]
            if current_signature(fn) != spec.signature:
                changed.append(
                    (name, spec.signature, current_signature(fn)))
        assert not missing, f"ops removed without schema update: {missing}"
        assert not changed, (
            "op signatures drifted from schema (regenerate via "
            f"tools/gen_op_schema.py if intentional): {changed}")

    def test_no_undeclared_public_ops(self):
        live = _live_surface()
        declared = set(all_ops())
        undeclared = sorted(n for n in live if n not in declared)
        assert not undeclared, (
            f"new public ops missing schema entries (run "
            f"tools/gen_op_schema.py): {undeclared}")

    def test_method_flag_matches_tensor(self):
        for name in all_ops():
            spec = get_op_info(name)
            if spec.is_method:
                assert hasattr(Tensor, name), (
                    f"schema says {name} is a Tensor method; it is not")

    def test_inplace_variants_exist(self):
        for name in all_ops():
            spec = get_op_info(name)
            if spec.inplace_variant:
                assert hasattr(Tensor, spec.inplace_variant), (
                    f"{name}: declared in-place variant "
                    f"{spec.inplace_variant} missing from Tensor")

    def test_registry_lookup(self):
        info = get_op_info("matmul")
        assert info.module == "math" and info.is_method
        with pytest.raises(KeyError):
            get_op_info("not_a_real_op")
        assert len(all_ops()) >= 300
