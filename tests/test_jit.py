"""jit.to_static: compiled forward, compiled full train step, state threading,
control flow, save/load export."""
import os

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu import jit
from paddle_tpu.optimizer import SGD, Adam
from paddle_tpu.optimizer.lr import StepDecay


def r(*shape):
    return np.random.rand(*shape).astype(np.float32)


class TestForward:
    def test_forward_matches_eager(self):
        net = nn.Sequential(nn.Linear(4, 8), nn.ReLU(), nn.Linear(8, 2))
        x = paddle.to_tensor(r(3, 4))
        eager = net(x).numpy()

        sfn = jit.to_static(lambda t: net(t))
        static = sfn(paddle.to_tensor(x.numpy())).numpy()
        np.testing.assert_allclose(eager, static, rtol=1e-5, atol=1e-6)

    def test_layer_decoration(self):
        net = nn.Linear(4, 2)
        net = jit.to_static(net)
        out = net(paddle.to_tensor(r(2, 4)))
        assert out.shape == [2, 2]

    def test_cache_by_shape(self):
        net = nn.Linear(4, 2)
        sfn = jit.to_static(lambda t: net(t))
        sfn(paddle.to_tensor(r(2, 4)))
        sfn(paddle.to_tensor(r(2, 4)))
        assert len(sfn._cache) == 1
        sfn(paddle.to_tensor(r(5, 4)))
        assert len(sfn._cache) == 2

    def test_weight_update_reflected(self):
        net = nn.Linear(2, 2)
        sfn = jit.to_static(lambda t: net(t))
        x = paddle.to_tensor(r(1, 2))
        out1 = sfn(x).numpy()
        net.weight.set_value(net.weight.numpy() * 2.0)
        out2 = sfn(x).numpy()
        assert not np.allclose(out1, out2)


class TestTrainStep:
    def test_full_train_step_compiles_and_learns(self):
        net = nn.Sequential(nn.Linear(4, 16), nn.ReLU(), nn.Linear(16, 2))
        opt = Adam(0.05, parameters=net.parameters())

        @jit.to_static
        def train_step(x, y):
            loss = paddle.nn.functional.cross_entropy(net(x), y)
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

        x = paddle.to_tensor(r(8, 4))
        y = paddle.to_tensor(np.random.randint(0, 2, (8,)).astype(np.int32))
        losses = [float(train_step(x, y).numpy()) for _ in range(25)]
        assert losses[-1] < losses[0] * 0.8
        # state stays concrete (no tracer leak)
        assert "Tracer" not in type(net[0].weight._value).__name__
        assert int(opt._global_state["step"]) == 25

    def test_matches_eager_training(self):
        paddle.seed(7)
        net_a = nn.Linear(3, 1)
        net_b = nn.Linear(3, 1)
        net_b.set_state_dict(net_a.state_dict())
        opt_a = SGD(0.1, parameters=net_a.parameters())
        opt_b = SGD(0.1, parameters=net_b.parameters())
        x = paddle.to_tensor(r(4, 3))

        @jit.to_static
        def step_b(t):
            loss = net_b(t).sum()
            loss.backward()
            opt_b.step()
            opt_b.clear_grad()
            return loss

        for _ in range(5):
            loss_a = net_a(x).sum()
            loss_a.backward()
            opt_a.step()
            opt_a.clear_grad()
            step_b(x)
        np.testing.assert_allclose(net_a.weight.numpy(), net_b.weight.numpy(),
                                   rtol=1e-4, atol=1e-5)

    def test_lr_schedule_no_retrace(self):
        net = nn.Linear(2, 1)
        sched = StepDecay(0.1, step_size=2, gamma=0.5)
        opt = SGD(sched, parameters=net.parameters())

        @jit.to_static
        def step(t):
            loss = net(t).sum()
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

        x = paddle.to_tensor(r(2, 2))
        for _ in range(6):
            step(x)
            sched.step()
        # one trace for the first call (accumulator creation), one after
        assert len(step._cache) <= 2

    def test_bn_buffers_update_under_jit(self):
        net = nn.Sequential(nn.Linear(4, 8), nn.BatchNorm1D(8))

        @jit.to_static
        def fwd(t):
            return net(t)

        m0 = net[1]._mean.numpy().copy()
        fwd(paddle.to_tensor(r(4, 4)))
        assert not np.allclose(m0, net[1]._mean.numpy())

    def test_rng_threads_through(self):
        drop = nn.Dropout(0.5)

        @jit.to_static
        def fwd(t):
            return drop(t)

        a = fwd(paddle.ones([8, 8])).numpy()
        b = fwd(paddle.ones([8, 8])).numpy()
        assert not np.array_equal(a, b)


class TestControlFlow:
    def test_cond(self):
        out = jit.cond(paddle.to_tensor(True), lambda a: a * 2,
                       lambda a: a * 3, paddle.ones([2]))
        np.testing.assert_array_equal(out.numpy(), [2, 2])

    def test_while_loop(self):
        i, s = jit.while_loop(lambda i, s: i < 5,
                              lambda i, s: (i + 1, s + i),
                              (paddle.to_tensor(0), paddle.to_tensor(0)))
        assert i.item() == 5 and s.item() == 10

    def test_scan(self):
        carry, ys = jit.scan(lambda c, x: (c + x, c),
                             paddle.to_tensor(0.0),
                             paddle.to_tensor(np.ones(5, np.float32)))
        assert carry.item() == 5.0

    def test_cond_inside_to_static(self):
        net = nn.Linear(2, 2)

        @jit.to_static
        def fwd(x, flag):
            h = net(x)
            return jit.cond(flag, lambda v: v * 2, lambda v: v, h)

        x = paddle.to_tensor(r(1, 2))
        a = fwd(x, paddle.to_tensor(True)).numpy()
        b = fwd(x, paddle.to_tensor(False)).numpy()
        np.testing.assert_allclose(a, b * 2, rtol=1e-6)


class TestDynamicShapeGuard:
    def test_nonzero_raises_under_trace(self):
        @jit.to_static
        def bad(x):
            return paddle.nonzero(x)

        with pytest.raises(Exception):
            bad(paddle.ones([3]))


class TestSaveLoad:
    def test_paddle_save_load(self, tmp_path):
        net = nn.Linear(3, 2)
        path = str(tmp_path / "model.pdparams")
        paddle.save(net.state_dict(), path)
        loaded = paddle.load(path)
        np.testing.assert_array_equal(loaded["weight"].numpy(),
                                      net.weight.numpy())
        net2 = nn.Linear(3, 2)
        net2.set_state_dict(loaded)
        np.testing.assert_array_equal(net2.weight.numpy(), net.weight.numpy())

    def test_jit_save_load_export(self, tmp_path):
        net = nn.Sequential(nn.Linear(4, 8), nn.ReLU(), nn.Linear(8, 2))
        path = str(tmp_path / "exported")
        jit.save(net, path, input_spec=[jit.InputSpec([2, 4], "float32")])
        loaded = jit.load(path)
        x = r(2, 4)
        out_ref = net(paddle.to_tensor(x)).numpy()
        out_loaded = loaded(paddle.to_tensor(x))
        np.testing.assert_allclose(np.asarray(out_loaded._value), out_ref,
                                   rtol=1e-5, atol=1e-6)

    def test_jit_save_converts_tensor_control_flow(self, tmp_path):
        """jit.save must run the same dy2static pass as to_static: a
        tensor-condition early return in forward previously hit a
        trace-time bool conversion during export (review r4)."""
        class Gate(nn.Layer):
            def __init__(self):
                super().__init__()
                self.lin = nn.Linear(4, 4)

            def forward(self, x):
                if paddle.sum(x) > 0.0:
                    return self.lin(x) * 2.0
                return self.lin(x)

        m = Gate()
        m.eval()
        path = str(tmp_path / "gate")
        jit.save(m, path, input_spec=[jit.InputSpec([2, 4], "float32")])
        loaded = jit.load(path)
        for sign in (1.0, -1.0):
            x = paddle.to_tensor(np.full((2, 4), sign, np.float32))
            np.testing.assert_allclose(loaded(x).numpy(), m(x).numpy(),
                                       rtol=1e-5)

    def test_optimizer_state_save_load(self, tmp_path):
        net = nn.Linear(2, 2)
        opt = Adam(0.01, parameters=net.parameters())
        net(paddle.ones([1, 2])).sum().backward()
        opt.step()
        path = str(tmp_path / "opt.pdopt")
        paddle.save(opt.state_dict(), path)
        loaded = paddle.load(path)
        assert loaded["@step"] == 1


class TestCompiledNanInfCheck:
    """FLAGS_check_nan_inf in COMPILED mode (VERDICT r1: the round-1 check
    was eager-only; reference hooks every op run, operator.cc:1270)."""

    def test_compiled_raises_on_nan(self):
        from paddle_tpu.core.flags import set_flags

        set_flags({"check_nan_inf": True})
        try:
            @jit.to_static
            def bad(x):
                return paddle.log(x)

            with pytest.raises(Exception, match="nan/inf"):
                out = bad(paddle.to_tensor(np.float32([-1.0])))
                out.numpy()  # sync in case the callback is async
        finally:
            set_flags({"check_nan_inf": False})

    def test_compiled_clean_passes(self):
        from paddle_tpu.core.flags import set_flags

        set_flags({"check_nan_inf": True})
        try:
            @jit.to_static
            def good(x):
                return paddle.log(x)

            out = good(paddle.to_tensor(np.float32([2.0])))
            np.testing.assert_allclose(out.numpy(), [np.log(2.0)],
                                       rtol=1e-6)
        finally:
            set_flags({"check_nan_inf": False})

    def test_eager_raises_on_inf(self):
        from paddle_tpu.core.flags import set_flags

        set_flags({"check_nan_inf": True})
        try:
            with pytest.raises(FloatingPointError, match="nan/inf"):
                paddle.divide(paddle.to_tensor([1.0]),
                              paddle.to_tensor([0.0]))
        finally:
            set_flags({"check_nan_inf": False})


class TestDy2StaticAST:
    """Minimal AST dy2static pass (VERDICT r3 #7; reference:
    dygraph_to_static/program_translator.py + convert_operators.py):
    data-dependent if/while over scalar tensors compile under to_static
    via jit.cond/jit.while_loop; Python-bool control flow and
    unsupported constructs keep their trace semantics."""

    def test_tensor_if_compiles_and_matches_eager(self):
        def f(x):
            if paddle.mean(x) > 0:
                y = x * 2.0
            else:
                y = x - 1.0
            return y

        st = jit.to_static(f)
        xp = paddle.to_tensor(np.array([1.0, 2.0], np.float32))
        xn = paddle.to_tensor(np.array([-1.0, -2.0], np.float32))
        np.testing.assert_allclose(st(xp).numpy(), f(xp).numpy())
        np.testing.assert_allclose(st(xn).numpy(), f(xn).numpy())
        # ONE executable serves both predicate values (it's a lax.cond,
        # not two traces specialized on a python bool)
        assert len(st._cache) == 1

    def test_tensor_while_compiles(self):
        def g(x):
            i = paddle.to_tensor(np.float32(0.0))
            while paddle.sum(x) < 100.0:
                x = x * 2.0
                i = i + 1.0
            return x, i

        st = jit.to_static(g)
        out, n = st(paddle.to_tensor(np.array([1.0, 2.0], np.float32)))
        np.testing.assert_allclose(n.numpy(), 6.0)
        np.testing.assert_allclose(out.numpy(), [64.0, 128.0])

    def test_python_bool_if_untouched_semantics(self):
        def f(x, flag):
            if flag:
                y = x + 1.0
            else:
                y = x - 1.0
            return y

        st = jit.to_static(f)
        x = paddle.to_tensor(np.array([1.0], np.float32))
        np.testing.assert_allclose(st(x, True).numpy(), [2.0])
        np.testing.assert_allclose(st(x, False).numpy(), [0.0])

    def test_nested_if_in_while(self):
        def f(x):
            s = paddle.to_tensor(np.float32(0.0))
            while paddle.sum(x) < 20.0:
                if paddle.mean(x) > 1.5:
                    x = x + 2.0
                else:
                    x = x * 3.0
                s = s + 1.0
            return x, s

        st = jit.to_static(f)
        x0 = np.array([1.0, 1.0], np.float32)

        def ref(x):
            s = 0.0
            while x.sum() < 20.0:
                if x.mean() > 1.5:
                    x = x + 2.0
                else:
                    x = x * 3.0
                s += 1.0
            return x, s

        out, s = st(paddle.to_tensor(x0))
        rx, rs = ref(x0)
        np.testing.assert_allclose(out.numpy(), rx)
        np.testing.assert_allclose(s.numpy(), rs)

    def test_translator_disable_runs_original_eagerly(self):
        calls = []

        def f(x):
            calls.append(1)
            if paddle.mean(x) > 0:
                y = x * 2.0
            else:
                y = x - 1.0
            return y

        st = jit.to_static(f)
        jit.ProgramTranslator.get_instance().enable(False)
        try:
            out = st(paddle.to_tensor(np.array([2.0], np.float32)))
            np.testing.assert_allclose(out.numpy(), [4.0])
        finally:
            jit.ProgramTranslator.get_instance().enable(True)

    def test_return_in_branch_falls_back(self):
        """return inside a branch is outside the minimal pass — the
        function must keep working for python-bool predicates (trace
        specializes on the bool, reference trace-fallback posture)."""
        def f(x, flag):
            if flag:
                return x * 2.0
            return x + 1.0

        st = jit.to_static(f)
        x = paddle.to_tensor(np.array([3.0], np.float32))
        np.testing.assert_allclose(st(x, True).numpy(), [6.0])
        np.testing.assert_allclose(st(x, False).numpy(), [4.0])

    def test_layer_method_converted(self):
        class Net(nn.Layer):
            def __init__(self):
                super().__init__()
                self.fc = nn.Linear(4, 4)

            def forward(self, x):
                h = self.fc(x)
                if paddle.mean(h) > 0:
                    out = paddle.tanh(h)
                else:
                    out = h * 0.5
                return out

        paddle.seed(0)
        net = Net()
        eager_pos = net(paddle.to_tensor(np.ones((2, 4), np.float32)))
        eager_neg = net(paddle.to_tensor(-np.ones((2, 4), np.float32)))
        paddle.seed(0)  # same init -> same weights as the eager net
        st2 = jit.to_static(Net())
        np.testing.assert_allclose(
            st2(paddle.to_tensor(np.ones((2, 4), np.float32))).numpy(),
            eager_pos.numpy(), atol=1e-6)
        np.testing.assert_allclose(
            st2(paddle.to_tensor(-np.ones((2, 4), np.float32))).numpy(),
            eager_neg.numpy(), atol=1e-6)

    def test_one_branch_assignment_clear_error(self):
        def f(x):
            if paddle.mean(x) > 0:
                y = x * 2.0
                tmp = x + 1.0  # noqa: F841 — branch-local, never merged
            else:
                y = x - 1.0
            return y

        st = jit.to_static(f)
        with pytest.raises(ValueError, match="tmp"):
            st(paddle.to_tensor(np.array([1.0], np.float32)))

    def test_gradients_flow_through_converted_if(self):
        """The tensor-pred if dispatches through the tape (lax.cond is
        jax-differentiable) — a bare jit.cond would return node-less
        Tensors and backward would silently produce no grads."""
        net = nn.Linear(4, 1)
        opt = SGD(0.1, parameters=net.parameters())

        @jit.to_static
        def step(x):
            loss = net(x).square().mean()
            if loss > 0.0:          # always true, but data-dependent
                scaled = loss * 2.0
            else:
                scaled = loss
            scaled.backward()
            opt.step()
            opt.clear_grad()
            return loss

        x = paddle.to_tensor(r(8, 4))
        losses = [float(step(x).numpy()) for _ in range(10)]
        assert losses[-1] < 0.5 * losses[0], losses

    def test_builtin_shadowing_local_rides_as_operand(self):
        """A local named `input` (shadowing the builtin — the standard
        paddle argument name) must still be a cond operand, or backward
        through the converted if silently drops the gradient chain."""
        net = nn.Linear(4, 1)
        opt = SGD(0.1, parameters=net.parameters())

        @jit.to_static
        def step(x):
            input = net(x).square().mean()  # noqa: A002
            if input > 0:
                scaled = input * 2.0
            else:
                scaled = input
            scaled.backward()
            opt.step()
            opt.clear_grad()
            return input

        x = paddle.to_tensor(r(8, 4))
        losses = [float(step(x).numpy()) for _ in range(10)]
        assert losses[-1] < 0.7 * losses[0], losses

    def test_closure_layer_read_in_branch(self):
        """A closure-captured layer called inside a branch stays closed
        over (never carried — the tuple-assign would shadow it)."""
        lin = nn.Linear(2, 2)

        @jit.to_static
        def f(x):
            if paddle.mean(x) > 0:
                y = lin(x)
            else:
                y = lin(x) * 0.5
            return y

        xp = paddle.to_tensor(np.ones((1, 2), np.float32))
        xn = paddle.to_tensor(-np.ones((1, 2), np.float32))
        ref = lin(xp).numpy()
        np.testing.assert_allclose(f(xp).numpy(), ref, atol=1e-6)
        np.testing.assert_allclose(f(xn).numpy(),
                                   lin(xn).numpy() * 0.5, atol=1e-6)

        @jit.to_static
        def g(x):
            while paddle.sum(x) < 10.0:
                x = lin(x).abs() + x + 1.0
            return x

        out = g(paddle.to_tensor(np.zeros((1, 2), np.float32)))
        assert float(out.sum().numpy()) >= 10.0

    def test_for_range_tensor_bound_compiles(self):
        """``for i in range(tensor_n)`` desugars to the while rewrite —
        ONE executable serves every trip count (XLA While, not unrolled
        retraces; reference: dygraph_to_static loop_transformer)."""
        def f(x, n):
            acc = paddle.zeros_like(x)
            for i in range(n):
                acc = acc + x * (i + 1)
            return acc

        st = jit.to_static(f)
        x = paddle.to_tensor(np.ones(3, np.float32))
        np.testing.assert_allclose(
            st(x, paddle.to_tensor(np.int32(4))).numpy(), 10.0)
        np.testing.assert_allclose(
            st(x, paddle.to_tensor(np.int32(2))).numpy(), 3.0)
        assert len(st._cache) == 1

    def test_for_range_start_step_variants(self):
        def g(x, n):
            s = paddle.zeros_like(x)
            for i in range(1, n, 2):
                s = s + i
            return s

        def down(x, n):
            s = paddle.zeros_like(x)
            for i in range(n, 0, -1):
                s = s + i
            return s

        x = paddle.to_tensor(np.zeros(2, np.float32))
        np.testing.assert_allclose(
            jit.to_static(g)(x, paddle.to_tensor(np.int32(6))).numpy(),
            float(sum(range(1, 6, 2))))
        np.testing.assert_allclose(
            jit.to_static(down)(x, paddle.to_tensor(np.int32(5))).numpy(),
            float(sum(range(5, 0, -1))))

    def test_for_range_nested_tensor_if_converts(self):
        """A rewritten nested if fabricates tuple-assign stores of every
        name it carries (incl. the loop var, which it reads); the
        rebinding bail must key on the ORIGINAL body's stores or the
        whole loop is left unconverted (review r4 finding #1)."""
        def f(x, n):
            s = paddle.zeros_like(x)
            for i in range(n):
                if paddle.sum(x) > -1.0:
                    s = s + i
            return s

        x = paddle.to_tensor(np.ones(2, np.float32))
        out = jit.to_static(f)(x, paddle.to_tensor(np.int32(4)))
        np.testing.assert_allclose(out.numpy(), float(sum(range(4))))

    def test_forward_wrapped_model_trains_with_external_backward(self):
        """The reference's CANONICAL to_static usage: wrap the MODEL
        (forward only), call backward + optimizer OUTSIDE.  The compiled
        call must be externally differentiable — it previously returned
        node-less tensors and silently trained at exactly zero update
        (review r4).  Early returns on a tensor condition convert too."""
        class Gate(nn.Layer):
            def __init__(self):
                super().__init__()
                self.lin = nn.Linear(4, 4)

            def forward(self, x):
                if paddle.sum(x) > 0.0:
                    return self.lin(x) * 2.0
                return self.lin(x)

        m = jit.to_static(Gate())
        opt = Adam(learning_rate=0.05, parameters=m.parameters())
        x = paddle.to_tensor(np.ones((2, 4), np.float32))
        losses = []
        for _ in range(15):
            loss = (m(x) ** 2).mean()
            loss.backward()
            opt.step()
            opt.clear_grad()
            losses.append(float(loss.numpy()))
        assert losses[-1] < losses[0], losses

        # input grads flow through a wrapped plain function as well
        @jit.to_static
        def f(t):
            return paddle.tanh(t) * 3.0

        t = paddle.to_tensor(np.array([0.5, -0.2], np.float32),
                             stop_gradient=False)
        f(t).sum().backward()
        np.testing.assert_allclose(
            t.grad.numpy(), 3.0 * (1 - np.tanh(t.numpy()) ** 2), rtol=1e-5)

    def test_forward_wrap_updates_bn_buffers(self):
        """Buffer mutations (BN running stats) still write back on the
        externally-differentiable path."""
        bnm = nn.Sequential(nn.Linear(4, 4), nn.BatchNorm1D(4))
        g = jit.to_static(lambda t: bnm(t))
        rm0 = bnm[1]._mean.numpy().copy()
        xb = paddle.to_tensor(np.random.RandomState(0)
                              .randn(8, 4).astype(np.float32))
        g(xb).sum().backward()
        assert not np.allclose(rm0, bnm[1]._mean.numpy())
        assert bnm[0].weight.grad is not None

    def test_rng_state_replays_compiled_randomness(self):
        """get/set_rng_state must capture the (seed, counter) pair that
        drives compiled-program step keys — restoring only the eager
        split chain silently broke dropout replay (review r4)."""
        drop = nn.Dropout(0.5)

        @jit.to_static
        def f(x):
            return drop(x)

        x = paddle.to_tensor(np.ones((16, 16), np.float32))
        st = paddle.get_rng_state()
        a = f(x).numpy()
        paddle.set_rng_state(st)
        b = f(x).numpy()
        c = f(x).numpy()
        np.testing.assert_allclose(a, b)
        assert not np.allclose(b, c)

    def test_tracer_list_gather_matches_eager(self):
        """x[[i, j]] with Tensor indices: the gather semantics must
        survive tracing (np.asarray raises on tracers; a tuple fallback
        silently became multi-axis x[i, j] — review r4)."""
        def g(x, i, j):
            return x[[i, j]]

        x = paddle.to_tensor(np.arange(12, dtype=np.float32).reshape(3, 4))
        i = paddle.to_tensor(np.int32(0))
        j = paddle.to_tensor(np.int32(2))
        eager = g(x, i, j).numpy()
        comp = jit.to_static(g)(x, i, j).numpy()
        assert eager.shape == (2, 4)
        np.testing.assert_allclose(eager, comp)

    def test_eval_mode_flip_selects_new_executable(self):
        """train/eval is part of the program: a .eval() after compiling
        in train mode must not keep running the train-mode executable
        (dropout kept dropping — review r4 composition probe)."""
        drop = nn.Dropout(0.5)

        @jit.to_static
        def f(x):
            return drop(x)

        x = paddle.to_tensor(np.random.RandomState(0)
                             .randn(16, 16).astype(np.float32))
        a = f(x).numpy()
        drop.eval()
        c = f(x).numpy()
        np.testing.assert_allclose(c, x.numpy())  # identity in eval
        drop.train()
        b = f(x).numpy()
        assert not np.allclose(a, b)  # fresh mask per train call
        assert len(f._cache) >= 2  # distinct executables per mode

    def test_loop_max_trips_trains_through_python_loops(self):
        """to_static(loop_max_trips=N): reference-style training scripts
        with data-dependent python loops (for-range over a Tensor, while
        over a Tensor condition) become differentiable — the dy2static
        rewrite lowers them to the bounded while (scan-of-cond)."""
        lin = nn.Linear(4, 4)
        opt = Adam(learning_rate=0.05, parameters=lin.parameters())

        @jit.to_static(loop_max_trips=8)
        def step(x, n):
            acc = paddle.zeros_like(x)
            for i in range(n):
                acc = acc + lin(x)
            loss = (acc * acc).mean()
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

        x = paddle.to_tensor(np.random.RandomState(0)
                             .randn(2, 4).astype(np.float32))
        n = paddle.to_tensor(np.int32(3))
        losses = [float(step(x, n).numpy()) for _ in range(15)]
        assert losses[-1] < losses[0], losses

        lin2 = nn.Linear(4, 4)
        opt2 = Adam(learning_rate=0.05, parameters=lin2.parameters())

        @jit.to_static(loop_max_trips=6)
        def step2(x):
            acc = paddle.zeros_like(x)
            k = paddle.to_tensor(np.float32(0))
            while paddle.sum(k) < 3.0:
                acc = acc + lin2(x)
                k = k + 1.0
            loss = (acc * acc).mean()
            loss.backward()
            opt2.step()
            opt2.clear_grad()
            return loss

        losses2 = [float(step2(x).numpy()) for _ in range(15)]
        assert losses2[-1] < losses2[0], losses2

    def test_while_loop_backward_raises_loudly(self):
        """XLA While has no static trip count — reverse mode CANNOT work.
        The reference's static While IS differentiable (while_grad
        stack), so silence here would be silently-zero training math;
        the loop rides the tape as one op whose vjp raises instead
        (review r4: verify drive caught constant loss over 20 steps)."""
        lin = nn.Linear(4, 4)
        x = paddle.to_tensor(np.ones((2, 4), np.float32))

        @jit.to_static
        def step(x, n):
            acc = paddle.zeros_like(x)
            for i in range(n):
                acc = acc + lin(x)
            loss = (acc * acc).mean()
            loss.backward()
            return loss

        with pytest.raises(NotImplementedError, match="while_loop"):
            step(x, paddle.to_tensor(np.int32(3)))

        # forward-only through the same machinery stays legal
        @jit.to_static
        def fwd(x, n):
            acc = paddle.zeros_like(x)
            for i in range(n):
                acc = acc + lin(x)
            return acc

        assert fwd(x, paddle.to_tensor(np.int32(2))).shape == [2, 4]

    def test_bounded_while_loop_differentiable(self):
        """maximum_trip_count=N lowers to a masked lax.scan — fully
        reverse-differentiable (TPU-native analog of the reference's
        while_grad stack); state freezes when the predicate goes false,
        truncates at N otherwise."""
        w = paddle.to_tensor(np.float32(0.5), stop_gradient=False)
        x = paddle.to_tensor(np.ones(3, np.float32) * 2.0,
                             stop_gradient=False)
        i, acc = jit.while_loop(
            lambda i, a: i < 3, lambda i, a: (i + 1, a + w * x),
            [paddle.to_tensor(np.int32(0)), paddle.zeros([3])],
            maximum_trip_count=8)
        assert int(i.numpy()) == 3
        acc.sum().backward()
        np.testing.assert_allclose(w.grad.numpy(), 18.0)   # 3 * sum(x)
        np.testing.assert_allclose(x.grad.numpy(), 1.5)    # 3 * w

        i, = jit.while_loop(lambda i: i < 100, lambda i: i + 1,
                            [paddle.to_tensor(np.int32(0))],
                            maximum_trip_count=5)
        assert int(i.numpy()) == 5  # truncation at the bound

    def test_bounded_while_no_nan_through_masked_iters(self):
        """The bound lowers to scan-of-cond, NOT a jnp.where mask: a body
        producing inf on the frozen post-termination state (t/0 here)
        must not poison gradients via the 0*inf where-NaN trap."""
        t = paddle.to_tensor(np.float32(2.0), stop_gradient=False)
        n = paddle.to_tensor(np.int32(3))
        _, acc = jit.while_loop(
            lambda i, a: i < n,
            lambda i, a: (i + 1, a + t / (n - i).astype("float32")),
            [paddle.to_tensor(np.int32(0)),
             paddle.to_tensor(np.float32(0.0))],
            maximum_trip_count=6)
        acc.backward()
        g = float(t.grad.numpy())
        assert np.isfinite(g)
        np.testing.assert_allclose(g, 1 / 3 + 1 / 2 + 1.0, rtol=1e-6)

    def test_bounded_while_trains_under_to_static(self):
        """The whole train step — bounded while + backward + optimizer —
        compiles and WEIGHT UPDATES PERSIST.  Regression: layers
        referenced only inside a nested body fn were invisible to
        to_static's state discovery (top-level co_names only), so their
        updates were silently discarded and call 2 crashed on the leaked
        trace tracer (review r4 verify drive)."""
        lin = nn.Linear(4, 4)
        opt = Adam(learning_rate=0.05, parameters=lin.parameters())
        w_before = lin.weight.numpy().copy()

        @jit.to_static
        def step(x, n):
            def body(i, acc):
                return i + 1, acc + lin(x)  # lin ONLY in the nested fn

            _, acc = jit.while_loop(lambda i, a: i < n, body,
                                    [paddle.to_tensor(np.int32(0)),
                                     paddle.zeros_like(x)],
                                    maximum_trip_count=6)
            loss = (acc * acc).mean()
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

        x = paddle.to_tensor(np.random.RandomState(0)
                             .randn(2, 4).astype(np.float32))
        n = paddle.to_tensor(np.int32(3))
        losses = [float(step(x, n).numpy()) for _ in range(15)]
        assert losses[-1] < losses[0], losses
        assert not np.allclose(lin.weight.numpy(), w_before)

    def test_scan_module_global_weights_get_grads(self):
        """Capture collection must see MODULE-GLOBAL layers too (not just
        closure cells): a script-level `lin = nn.Linear(...)` used inside
        a scan body is the same silently-no-grad trap (review r4)."""
        import tests._scan_global_helper as helper

        g = helper.run_scan_and_grad()
        assert g is not None and float(g) > 0.0

    def test_for_range_star_args_left_untouched(self):
        """range(*b) can't be rewritten (the setup assign would be a
        SyntaxError killing conversion of the WHOLE function); the loop
        stays python-level and the tensor-if in the same function still
        converts (review r4 finding #2)."""
        def g(x, flag):
            b = (0, 3)
            for i in range(*b):
                x = x + 1.0
            if paddle.sum(flag) > 0.0:
                x = x * 2.0
            return x

        x = paddle.to_tensor(np.ones(2, np.float32))
        out = jit.to_static(g)(x, paddle.to_tensor(np.float32(1.0)))
        np.testing.assert_allclose(out.numpy(), 8.0)

    def test_for_python_range_still_unrolls(self):
        # static trip count keeps plain-trace semantics (no rewrite cost,
        # and `break` etc. stay legal there)
        def h(x):
            for i in range(3):
                x = x * 2.0
            return x

        out = jit.to_static(h)(paddle.to_tensor(np.ones(2, np.float32)))
        np.testing.assert_allclose(out.numpy(), 8.0)

    def test_for_range_python_semantics_preserved(self):
        """Plain-int ranges run a REAL python for inside the converter —
        loop-var binding, empty-range prior binding, step=0 ValueError,
        and bound-evaluation order are exactly eager's (review r4)."""
        def overshoot(x):
            for i in range(3):
                x = x + 1.0
            return x * i  # last ITERATED value (2), not last+step (3)

        x = paddle.to_tensor(np.ones(2, np.float32))
        np.testing.assert_allclose(
            jit.to_static(overshoot)(x).numpy(), overshoot(x).numpy())

        def empty_prior(x):
            i = 99
            for i in range(0):
                x = x + 1.0
            return x + i  # prior binding survives the empty range

        np.testing.assert_allclose(
            jit.to_static(empty_prior)(x).numpy(), 100.0)

        def stepzero(x):
            for i in range(1, 5, 0):
                x = x + 1.0
            return x

        with pytest.raises(ValueError):
            jit.to_static(stepzero)(x)

        order = []

        def s1():
            order.append("start")
            return 0

        def s2():
            order.append("stop")
            return 2

        def sidefx(x):
            for i in range(s1(), s2()):
                x = x + 1.0
            return x

        jit.to_static(sidefx)(x)
        assert order == ["start", "stop"]

    def test_for_shadowed_range_untouched(self):
        def shadowed(x):
            range = lambda n: [10.0]  # noqa: E731,A001
            for i in range(2):
                x = x + i
            return x

        x = paddle.to_tensor(np.ones(2, np.float32))
        np.testing.assert_allclose(
            jit.to_static(shadowed)(x).numpy(), 11.0)

    def test_for_over_list_untouched(self):
        def f(x):
            for m in [1.0, 2.0, 3.0]:
                x = x * m
            return x

        out = jit.to_static(f)(paddle.to_tensor(np.ones(2, np.float32)))
        np.testing.assert_allclose(out.numpy(), 6.0)

    def test_side_effecting_python_while_condition(self):
        """The python-bool path must not re-evaluate a side-effecting
        condition for the first test (an extra call would silently skip
        an iteration)."""
        calls = []

        @jit.to_static
        def f(x):
            s = x * 0.0
            while len(calls) < 3 and (calls.append(1) or True):
                s = s + 1.0
            return s

        out = f(paddle.to_tensor(np.float32(0.0)))
        np.testing.assert_allclose(out.numpy(), 3.0)


class TestControlFlowGrads:
    """jit.cond and jit.scan dispatch through the tape (lax.cond/scan are
    jax-differentiable) so backward reaches their tensor operands."""

    def test_cond_backward(self):
        x = paddle.to_tensor(np.array([2.0, 3.0], np.float32),
                             stop_gradient=False)
        out = jit.cond(paddle.to_tensor(True),
                       lambda a: (a * a).sum(),
                       lambda a: a.sum(), x)
        out.backward()
        np.testing.assert_allclose(x.grad.numpy(), [4.0, 6.0])
        x2 = paddle.to_tensor(np.array([2.0, 3.0], np.float32),
                              stop_gradient=False)
        out2 = jit.cond(paddle.to_tensor(False),
                        lambda a: (a * a).sum(),
                        lambda a: a.sum(), x2)
        out2.backward()
        np.testing.assert_allclose(x2.grad.numpy(), [1.0, 1.0])

    def test_scan_backward(self):
        xs = paddle.to_tensor(np.arange(1, 5, dtype=np.float32),
                              stop_gradient=False)
        carry, ys = jit.scan(lambda c, x: (c * x, c),
                             paddle.to_tensor(np.float32(1.0)), xs)
        carry.backward()  # carry = prod(xs); d/dxi = prod/xi
        np.testing.assert_allclose(xs.grad.numpy(),
                                   [24.0, 12.0, 8.0, 6.0])

    def test_cond_under_to_static_trains(self):
        net = nn.Linear(4, 1)
        opt = SGD(0.1, parameters=net.parameters())

        @jit.to_static
        def step(x):
            loss = net(x).square().mean()
            scaled = jit.cond(loss > 0.0,
                              lambda v: v * 2.0, lambda v: v, loss)
            scaled.backward()
            opt.step()
            opt.clear_grad()
            return loss

        x = paddle.to_tensor(r(8, 4))
        losses = [float(step(x).numpy()) for _ in range(10)]
        assert losses[-1] < 0.5 * losses[0], losses

    def test_closure_captured_weights_get_grads(self):
        """Branches/bodies closing over layer weights (the RNN-cell
        pattern) must receive gradients: captured tensors are promoted
        to tape operands and functionally substituted during the trace."""
        w = paddle.to_tensor(np.float32(2.0), stop_gradient=False)
        x = paddle.to_tensor(np.array([3.0], np.float32),
                             stop_gradient=False)
        out = jit.cond(paddle.to_tensor(True),
                       lambda a: (a * w).sum(), lambda a: a.sum(), x)
        out.backward()
        np.testing.assert_allclose(x.grad.numpy(), [2.0])
        np.testing.assert_allclose(w.grad.numpy(), 3.0)

        w2 = paddle.to_tensor(np.float32(0.5), stop_gradient=False)
        init = paddle.to_tensor(np.float32(1.0), stop_gradient=False)
        xs = paddle.to_tensor(np.ones(3, np.float32))
        carry, _ = jit.scan(lambda c, t: (c * w2 + t, c), init, xs)
        carry.backward()
        # carry = ((1*w+1)*w+1)*w+1 = w^3 + w^2 + w + 1; d/dw = 3w^2+2w+1
        np.testing.assert_allclose(w2.grad.numpy(),
                                   3 * 0.25 + 2 * 0.5 + 1, rtol=1e-6)
        np.testing.assert_allclose(init.grad.numpy(), 0.125)

    def test_rnn_scan_cell_trains(self):
        cell = nn.Linear(4, 4)
        opt = SGD(0.05, parameters=cell.parameters())
        xs = paddle.to_tensor(np.random.RandomState(0)
                              .randn(5, 2, 4).astype(np.float32))
        init = paddle.to_tensor(np.zeros((2, 4), np.float32))

        losses = []
        for _ in range(50):
            carry, _ = jit.scan(
                lambda c, x: (paddle.tanh(cell(c) + x), c), init, xs)
            loss = carry.square().mean()
            loss.backward()
            opt.step()
            opt.clear_grad()
            losses.append(float(loss.numpy()))
        assert losses[-1] < 0.6 * losses[0], losses


class TestBreakContinueReturn:
    """dy2static break/continue/return transforms (reference:
    dygraph_to_static/break_continue_transformer.py loop-carried boolean
    guards, return_transformer.py return-flag + result carry).  The
    VERDICT r4 gap: these used to silently trace-fall-back, turning
    data-dependent predicates into ConcretizationTypeErrors."""

    def test_while_break_tensor_condition_trains(self):
        """while + break over a Tensor condition compiles AND trains —
        the gradient flows through the break guard's masked iterations."""
        lin = nn.Linear(4, 4)
        opt = SGD(learning_rate=0.01, parameters=lin.parameters())

        @jit.to_static(loop_max_trips=12)
        def step(x, n):
            s = paddle.zeros_like(x)
            i = paddle.to_tensor(np.asarray(0, np.int32))
            while i < n:
                s = s + lin(x)
                if s.sum() > 6.0:
                    break
                i = i + 1
            loss = ((s - 1.0) ** 2).mean()
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

        x = paddle.to_tensor(np.random.RandomState(0)
                             .randn(2, 4).astype(np.float32))
        n = paddle.to_tensor(np.asarray(4, np.int32))
        losses = [float(np.asarray(step(x, n).numpy())) for _ in range(10)]
        assert losses[-1] < losses[0], losses

    def test_while_break_fires_at_right_iteration(self):
        @jit.to_static(loop_max_trips=12)
        def count_until(x, n, thresh):
            s = paddle.zeros_like(x)
            i = paddle.to_tensor(np.asarray(0, np.int32))
            while i < n:
                s = s + x
                i = i + 1
                if s.sum() >= thresh:
                    break
            return i

        c = count_until(paddle.to_tensor(np.ones(2, np.float32)),
                        paddle.to_tensor(np.asarray(10, np.int32)),
                        paddle.to_tensor(np.asarray(5.9, np.float32)))
        assert int(np.asarray(c.numpy())) == 3  # 2 per iter: 2, 4, 6

    def test_for_range_continue_tensor_bound(self):
        @jit.to_static(loop_max_trips=16)
        def f(x, n):
            acc = x * 0.0
            for i in range(n):
                if i % 2 == 0:
                    continue
                acc = acc + x * i
            return acc

        out = f(paddle.to_tensor(np.ones(3, np.float32)),
                paddle.to_tensor(np.asarray(6, np.int32)))
        np.testing.assert_allclose(np.asarray(out.numpy()), 9.0)  # 1+3+5

    def test_for_range_break_tensor_bound(self):
        @jit.to_static(loop_max_trips=16)
        def f(x, n):
            acc = x * 0.0
            for i in range(n):
                acc = acc + x
                if acc.sum() >= 6.0:
                    break
            return acc

        out = f(paddle.to_tensor(np.ones(2, np.float32)),
                paddle.to_tensor(np.asarray(10, np.int32)))
        np.testing.assert_allclose(np.asarray(out.numpy()), 3.0)

    def test_python_loop_break_exact_semantics(self):
        @jit.to_static
        def f(x):
            i = 0
            while i < 100:
                i += 1
                if i >= 5:
                    break
            return x + i

        out = f(paddle.to_tensor(np.zeros(1, np.float32)))
        np.testing.assert_allclose(np.asarray(out.numpy()), 5.0)

    def test_return_inside_python_loop(self):
        """Return-flag lowering: the loop condition picks up `not retf`
        and trailing statements are guarded."""
        @jit.to_static
        def f(x):
            for i in range(10):
                x = x + 1.0
                if i == 3:
                    return x * 2.0
            return x

        out = f(paddle.to_tensor(np.zeros(2, np.float32)))
        np.testing.assert_allclose(np.asarray(out.numpy()), 8.0)

    def test_return_inside_tensor_loop_raises_actionably(self):
        @jit.to_static(loop_max_trips=8)
        def f(x, n):
            i = paddle.to_tensor(np.asarray(0, np.int32))
            while i < n:
                if i > 2:
                    return x * 2.0
                i = i + 1
            return x

        with pytest.raises(ValueError, match="loop-carried"):
            f(paddle.to_tensor(np.ones(2, np.float32)),
              paddle.to_tensor(np.asarray(5, np.int32)))

    def test_tensor_if_early_return_trains(self):
        lin = nn.Linear(3, 3)
        opt = SGD(learning_rate=0.05, parameters=lin.parameters())

        @jit.to_static
        def f(x):
            h = lin(x)
            if h.sum() > 0:
                return (h * h).mean()
            return ((h - 1) * (h - 1)).mean()

        x = paddle.to_tensor(np.ones((2, 3), np.float32))
        losses = []
        for _ in range(8):
            loss = f(x)
            loss.backward()
            opt.step()
            opt.clear_grad()
            losses.append(float(np.asarray(loss.numpy())))
        assert losses[-1] < losses[0], losses

    def test_nested_loop_break_binds_to_inner(self):
        """A break in a nested python loop must not leak into the outer
        converted loop's flags."""
        @jit.to_static
        def f(x):
            total = 0
            for i in range(3):
                for j in range(5):
                    if j == 1:
                        break
                    total = total + 1
            return x + total

        out = f(paddle.to_tensor(np.zeros(1, np.float32)))
        np.testing.assert_allclose(np.asarray(out.numpy()), 3.0)


class TestBareTensorState:
    def test_bare_parameter_trains_under_to_static(self):
        """A plain Tensor handed to the optimizer (no Layer) is state:
        pre-r5 the update was silently lost and the live value leaked a
        tracer (found by the round-5 probe drives)."""
        w = paddle.to_tensor(np.asarray([0.5], np.float32))
        w.stop_gradient = False
        opt = SGD(learning_rate=0.005, parameters=[w])

        @jit.to_static
        def step(x):
            loss = ((x * w - 3.0) ** 2).sum()
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

        x = paddle.to_tensor(np.ones(4, np.float32))
        losses = [float(np.asarray(step(x).numpy())) for _ in range(10)]
        assert losses[-1] < losses[0], losses
        # live value is concrete (no leaked tracer) and has moved
        val = float(np.asarray(w.numpy())[0])
        assert val != 0.5

    def test_param_group_dict_bare_tensor_trains(self):
        """Bare tensors nested in parameter-GROUP dicts thread as state
        too (review r5 follow-up)."""
        w = paddle.to_tensor(np.asarray([0.5], np.float32))
        w.stop_gradient = False
        opt = SGD(learning_rate=0.005,
                  parameters=[{"params": [w]}])

        @jit.to_static
        def step(x):
            loss = ((x * w - 3.0) ** 2).sum()
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

        x = paddle.to_tensor(np.ones(4, np.float32))
        losses = [float(np.asarray(step(x).numpy())) for _ in range(10)]
        assert losses[-1] < losses[0], losses


class TestSlotOrder:
    def test_opt_slots_follow_parameter_order_not_object_ids(self):
        """The slot walk is the compiled step's argument order.  By
        id(param) it followed object addresses, so the same script
        lowered to a different program each run and the persistent
        compile cache never hit (seen on the chip, PR 22)."""
        from paddle_tpu.jit import _State

        first, second = nn.Linear(4, 4), nn.Linear(4, 4)
        layers = [second, first]        # against creation (and id) order
        opt = Adam(1e-3, parameters=[p for l in layers
                                     for p in l.parameters()])
        x = paddle.to_tensor(np.ones((2, 4), np.float32))
        (first(x).sum() + second(x).sum()).backward()
        opt.step()
        state = _State(layers, [opt])
        want = [id(p) for p in state.params]
        for name in ("moment1", "moment2"):
            store = opt._accumulators[name]
            got = [k for s, k in state.opt_slots() if s is store]
            assert got == want
