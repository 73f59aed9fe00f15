"""CustomOp tests — reference tests/python/unittest/test_operator.py
(test_custom_op) over python/mxnet/operator.py."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import operator as op_mod


@op_mod.register("pysoftmax")
class PySoftmaxProp(op_mod.CustomOpProp):
    def __init__(self):
        super().__init__(need_top_grad=False)

    def list_arguments(self):
        return ["data", "label"]

    def list_outputs(self):
        return ["output"]

    def infer_shape(self, in_shape):
        data_shape = in_shape[0]
        label_shape = (in_shape[0][0],)
        return [data_shape, label_shape], [data_shape], []

    def create_operator(self, ctx, shapes, dtypes):
        return PySoftmax()


class PySoftmax(op_mod.CustomOp):
    def forward(self, is_train, req, in_data, out_data, aux):
        x = in_data[0].asnumpy()
        y = np.exp(x - x.max(axis=1, keepdims=True))
        y /= y.sum(axis=1, keepdims=True)
        self.assign(out_data[0], req[0], mx.nd.array(y))

    def backward(self, req, out_grad, in_data, out_data, in_grad, aux):
        l = in_data[1].asnumpy().ravel().astype(np.int64)
        y = out_data[0].asnumpy()
        y[np.arange(l.shape[0]), l] -= 1.0
        self.assign(in_grad[0], req[0], mx.nd.array(y))


@op_mod.register("scalemul")
class ScaleMulProp(op_mod.CustomOpProp):
    """Exercises kwargs → prop constructor string marshalling."""

    def __init__(self, scale="1.0"):
        super().__init__(need_top_grad=True)
        self.scale = float(scale)

    def create_operator(self, ctx, shapes, dtypes):
        s = self.scale

        class _Op(op_mod.CustomOp):
            def forward(self, is_train, req, in_data, out_data, aux):
                self.assign(out_data[0], req[0], in_data[0] * s)

            def backward(self, req, out_grad, in_data, out_data, in_grad, aux):
                self.assign(in_grad[0], req[0], out_grad[0] * s)

        return _Op()


def test_custom_nd_forward():
    x = mx.nd.array(np.random.rand(4, 10).astype(np.float32))
    lbl = mx.nd.array(np.zeros(4, np.float32))
    out = mx.nd.Custom(x, lbl, op_type="pysoftmax")
    xn = x.asnumpy()
    ref = np.exp(xn - xn.max(1, keepdims=True))
    ref /= ref.sum(1, keepdims=True)
    np.testing.assert_allclose(out.asnumpy(), ref, rtol=1e-5)


def test_custom_kwargs_and_grad():
    x = mx.nd.array(np.arange(6, dtype=np.float32).reshape(2, 3))
    x.attach_grad()
    with mx.autograd.record():
        y = mx.nd.Custom(x, op_type="scalemul", scale=3.0)
        loss = y.sum()
    loss.backward()
    np.testing.assert_allclose(x.grad.asnumpy(), np.full((2, 3), 3.0),
                               rtol=1e-6)


def test_custom_symbol_trains_via_module():
    # Xavier's draw and the iterator's shuffle take the global streams:
    # seeded, or the last error depends on what ran before in the worker
    mx.random.seed(0)
    np.random.seed(0)
    rs = np.random.RandomState(0)
    X = rs.rand(64, 8).astype(np.float32)
    w_true = rs.rand(8, 3).astype(np.float32)
    y = (X @ w_true).argmax(axis=1).astype(np.float32)

    data = mx.sym.Variable("data")
    label = mx.sym.Variable("softmax_label")
    fc = mx.sym.FullyConnected(data, num_hidden=3, name="fc")
    net = mx.sym.Custom(fc, label, op_type="pysoftmax", name="pysm")
    net = mx.sym.MakeLoss(net, name="out")

    mod = mx.mod.Module(net, data_names=["data"],
                        label_names=["softmax_label"], context=mx.cpu())
    it = mx.io.NDArrayIter(X, y, batch_size=16, shuffle=True,
                           label_name="softmax_label")
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params(mx.init.Xavier())
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.5})
    first_err = None
    for _ in range(12):
        it.reset()
        for batch in it:
            mod.forward(batch, is_train=True)
            probs = mod.get_outputs()[0].asnumpy()
            err = (probs.argmax(1) != batch.label[0].asnumpy()).mean()
            if first_err is None:
                first_err = err
            mod.backward()
            mod.update()
    assert err < first_err, (first_err, err)
    assert err < 0.2, err


def test_custom_symbol_infer_shape():
    data = mx.sym.Variable("data")
    label = mx.sym.Variable("label")
    net = mx.sym.Custom(data, label, op_type="pysoftmax")
    arg_shapes, out_shapes, _ = net.infer_shape(data=(5, 7), label=(5,))
    assert out_shapes[0] == (5, 7)
    assert net.list_arguments() == ["data", "label"]


def test_custom_unregistered_raises():
    x = mx.nd.array(np.ones((2, 2), np.float32))
    with pytest.raises(mx.MXNetError):
        mx.nd.Custom(x, op_type="no_such_op")


@op_mod.register("intgather")
class IntGatherProp(op_mod.CustomOpProp):
    """Integer second input (indices) — its grad must be float0-dropped,
    not returned as int zeros (custom_vjp contract)."""

    def __init__(self):
        super().__init__(need_top_grad=True)

    def list_arguments(self):
        return ["data", "idx"]

    def infer_shape(self, in_shape):
        return in_shape, [(in_shape[1][0], in_shape[0][1])], []

    def create_operator(self, ctx, shapes, dtypes):
        class _Op(op_mod.CustomOp):
            def forward(self, is_train, req, in_data, out_data, aux):
                x = in_data[0].asnumpy()
                i = in_data[1].asnumpy().astype(np.int64)
                self.assign(out_data[0], req[0], mx.nd.array(x[i]))

            def backward(self, req, out_grad, in_data, out_data, in_grad, aux):
                g = np.zeros(in_data[0].shape, np.float32)
                i = in_data[1].asnumpy().astype(np.int64)
                np.add.at(g, i, out_grad[0].asnumpy())
                self.assign(in_grad[0], req[0], mx.nd.array(g))
                self.assign(in_grad[1], req[1],
                            mx.nd.zeros(in_data[1].shape))

        return _Op()


def test_custom_op_integer_input_grad():
    x = mx.nd.array(np.arange(12, dtype=np.float32).reshape(4, 3))
    idx = mx.nd.array(np.array([1, 3], dtype=np.int64), dtype="int64")
    x.attach_grad()
    with mx.autograd.record():
        y = mx.nd.Custom(x, idx, op_type="intgather")
        loss = y.sum()
    loss.backward()
    expect = np.zeros((4, 3), np.float32)
    expect[[1, 3]] = 1.0
    np.testing.assert_allclose(x.grad.asnumpy(), expect)


def test_custom_op_inside_ctx_group_scope():
    with mx.AttrScope(ctx_group="dev1"):
        sym = mx.sym.Custom(mx.sym.Variable("data"), op_type="scalemul",
                            scale="3.0")
    ex = sym.bind(mx.cpu(0), args={"data": mx.nd.ones((2, 2))})
    np.testing.assert_allclose(ex.forward()[0].asnumpy(),
                               3 * np.ones((2, 2)))
