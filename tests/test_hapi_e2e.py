"""M1 end-to-end: MNIST via Model.fit (BASELINE config #1; call-stack parity
with /root/reference SURVEY §3.3). Uses a small MLP to keep XLA:CPU compile
time CI-friendly; the full LeNet config is the verify skill's canonical
drive (.claude/skills/verify/SKILL.md)."""
import numpy as np

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.io import DataLoader, Dataset
from paddle_tpu.vision.datasets import MNIST


class MLP(nn.Layer):
    def __init__(self):
        super().__init__()
        self.fc1 = nn.Linear(784, 64)
        self.fc2 = nn.Linear(64, 10)

    def forward(self, x):
        x = paddle.reshape(x, [x.shape[0], -1])
        return self.fc2(nn.functional.relu(self.fc1(x)))


def _make_model(lr=1e-3):
    net = MLP()
    model = paddle.Model(net)
    model.prepare(
        optimizer=paddle.optimizer.Adam(parameters=net.parameters(), learning_rate=lr),
        loss=paddle.nn.CrossEntropyLoss(),
        metrics=paddle.metric.Accuracy(),
    )
    return model, net


def test_fit_learns_and_evaluates(tmp_path):
    paddle.seed(0)
    model, net = _make_model()
    train = MNIST(mode="train")
    hist = model.fit(train, batch_size=256, epochs=3, verbose=0)
    accs = [float(np.atleast_1d(v)[0]) for v in hist.history["acc"]]
    assert accs[-1] > accs[0], f"did not learn: {accs}"
    assert accs[-1] > 0.5

    ev = model.evaluate(MNIST(mode="test"), batch_size=256, verbose=0)
    assert float(np.atleast_1d(ev["acc"])[0]) > 0.5

    # save / load roundtrip
    path = str(tmp_path / "ckpt")
    model.save(path)
    model2, net2 = _make_model()
    model2.load(path)
    np.testing.assert_array_equal(net2.fc1.weight.numpy(), net.fc1.weight.numpy())

    # predict drops the label column and returns class scores
    preds = model2.predict(MNIST(mode="test"), batch_size=512, stack_outputs=True)
    assert preds[0].shape == (512, 10)
    acc = (preds[0].argmax(-1) == MNIST(mode="test").labels).mean()
    assert acc > 0.5


def test_train_batch_api():
    paddle.seed(0)
    model, _ = _make_model()
    x = np.random.rand(32, 1, 28, 28).astype(np.float32)
    y = np.random.randint(0, 10, (32,)).astype(np.int64)
    loss1, _ = model.train_batch([x], [y])
    for _ in range(5):
        loss2, _ = model.train_batch([x], [y])
    assert loss2[0] < loss1[0]  # overfits a fixed batch


def test_early_stopping_and_callbacks():
    paddle.seed(0)
    model, _ = _make_model(lr=0.0)  # lr=0 => no improvement => stops early
    es = paddle.hapi.callbacks.EarlyStopping(monitor="loss", patience=0, mode="min")
    train = MNIST(mode="train")
    test = MNIST(mode="test")
    hist = model.fit(train, eval_data=test, batch_size=512, epochs=5, verbose=0, callbacks=[es])
    assert len(hist.history["loss"]) < 5  # stopped before all epochs


def test_paddle_save_load_nested(tmp_path):
    obj = {"a": paddle.ones([2, 2]), "b": [paddle.zeros([3]), {"c": 1.5}]}
    p = str(tmp_path / "obj.pd")
    paddle.save(obj, p)
    loaded = paddle.load(p)
    np.testing.assert_array_equal(loaded["a"].numpy(), np.ones((2, 2)))
    assert loaded["b"][1]["c"] == 1.5


def test_dataloader():
    class Sq(Dataset):
        def __len__(self):
            return 10

        def __getitem__(self, i):
            return np.float32(i), np.int64(i * i)

    dl = DataLoader(Sq(), batch_size=4, drop_last=False)
    batches = list(dl)
    assert len(batches) == 3
    assert batches[0][0].shape == [4]
    assert batches[-1][0].shape == [2]
    # prefetch-thread path yields identical content
    dl2 = DataLoader(Sq(), batch_size=4, num_workers=2)
    b2 = list(dl2)
    np.testing.assert_array_equal(b2[0][1].numpy(), batches[0][1].numpy())


def test_datasets_long_tail():
    """Imikolov / Conll05st / Flowers (VERDICT r3 missing #9)."""
    from paddle_tpu.text import Conll05st, Imikolov
    from paddle_tpu.vision.datasets import Flowers

    ng = Imikolov(data_type="NGRAM", window_size=5)
    assert ng[0].shape == (5,) and ng[0].dtype == np.int64
    # markov structure: the bigram successor must dominate
    import collections
    succ = collections.Counter()
    for i in range(2000):
        succ[(int(ng[i][0]), int(ng[i][1]))] += 1
    top = succ.most_common(1)[0][1]
    assert top > 3  # deterministic successor repeats; uniform noise wouldn't

    sq = Imikolov(data_type="SEQ", mode="test")
    assert sq[0].shape == (20,)

    c = Conll05st()
    item = c[0]
    assert len(item) == 9
    assert all(a.shape == (Conll05st.SEQ,) for a in item)
    w, p, l = c.get_dict()
    assert len(l) == Conll05st.NUM_LABELS
    # the mark vector flags exactly one predicate
    assert int(item[7].sum()) == 1

    f = Flowers(mode="test")
    img, lbl = f[0]
    assert img.shape == (3, 32, 32) or img.shape == (32, 32, 3)
    assert 0 <= int(lbl) < 102
    assert len(Flowers(mode="train")) == 2040


def test_model_summary_table(capsys):
    from paddle_tpu.vision.models import LeNet

    model = paddle.Model(LeNet())
    rep = model.summary(input_size=(1, 1, 28, 28))
    out = capsys.readouterr().out
    assert "Layer (type)" in out and "Param #" in out
    assert rep["total_params"] > 0
    assert "layers" in rep and len(rep["layers"]) >= 3
    # conv layers report their output shapes
    assert any("Conv2D" in r["name"] for r in rep["layers"])
    assert all(isinstance(r["output_shape"], list) for r in rep["layers"])
