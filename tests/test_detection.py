"""SSD detection family (reference: GluonCV ssd + contrib multibox ops)."""
import os

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, nd
from mxnet_tpu.models import (MultiBoxDetection, MultiBoxTarget,
                              SSDMultiBoxLoss, generate_anchors, ssd_lite)
from mxnet_tpu.test_utils import assert_almost_equal


def test_generate_anchors():
    anchors = generate_anchors([(2, 2)], 64, [(0.5, 0.7)], [[1, 2]])
    # 2x2 cells x (2 + 2 for ratio 2) = 16 anchors
    assert anchors.shape == (16, 4)
    # first anchor centered at (0.25, 0.25) with w=h=0.5
    assert_almost_equal(anchors[0], [0.0, 0.0, 0.5, 0.5], atol=1e-6)


def test_multibox_target_matching():
    anchors = nd.array(onp.array(
        [[0.0, 0.0, 0.4, 0.4], [0.5, 0.5, 0.9, 0.9], [0.0, 0.6, 0.3, 0.9]],
        dtype="float32"))
    labels = nd.array(onp.array(
        [[[1, 0.05, 0.05, 0.45, 0.45]]], dtype="float32"))
    bt, bm, ct = MultiBoxTarget(anchors, labels)
    ct_np = ct.asnumpy()[0]
    assert ct_np[0] == 2.0          # matched -> class 1 + 1 offset
    assert ct_np[1] == 0.0          # background
    assert bm.asnumpy()[0, :4].sum() == 4.0  # first anchor's coords masked in


@pytest.mark.slow
def test_ssd_train_and_detect():
    mx.random.seed(0)
    net = ssd_lite(num_classes=3, image_size=64)
    net.initialize()
    x = nd.random.normal(shape=(2, 3, 64, 64))
    cls_pred, box_pred = net(x)
    N = cls_pred.shape[1]
    assert box_pred.shape == (2, N, 4)
    anchors = net.anchors
    assert anchors.shape == (N, 4)

    labels = nd.array(onp.array([
        [[0, 0.1, 0.1, 0.4, 0.4], [-1, 0, 0, 0, 0]],
        [[2, 0.5, 0.5, 0.9, 0.9], [1, 0.2, 0.6, 0.4, 0.8]]],
        dtype="float32"))
    bt, bm, ct = MultiBoxTarget(anchors, labels)
    lossfn = SSDMultiBoxLoss()
    trainer = mx.gluon.Trainer(net.collect_params(), "sgd",
                               {"learning_rate": 0.05, "momentum": 0.9})
    losses = []
    for _ in range(12):
        with autograd.record():
            cp, bp = net(x)
            total, cl, bl = lossfn(cp, bp, ct, bt, bm)
            loss = total.mean()
        loss.backward()
        trainer.step(2)
        losses.append(float(loss.asscalar()))
    assert losses[-1] < losses[0]

    dets = net.detect(x, topk=50)
    assert dets.shape == (2, 50, 6)
    d = dets.asnumpy()
    valid = d[d[..., 0] >= 0]
    if len(valid):
        assert ((valid[:, 1] >= 0) & (valid[:, 1] <= 1)).all()


def test_estimator_fit():
    from mxnet_tpu.gluon import nn, Trainer, loss as gloss
    from mxnet_tpu.gluon.contrib.estimator import (Estimator,
                                                   EarlyStoppingHandler,
                                                   LoggingHandler)
    from mxnet_tpu.gluon.data import ArrayDataset, DataLoader
    rng = onp.random.RandomState(0)
    X = rng.randn(128, 8).astype("float32")
    W = rng.randn(3, 8).astype("float32")
    Y = (X @ W.T).argmax(1).astype("float32")
    net = nn.Sequential()
    net.add(nn.Dense(16, activation="relu"), nn.Dense(3))
    net.initialize()
    est = Estimator(net, gloss.SoftmaxCrossEntropyLoss(),
                    train_metrics="acc",
                    trainer=Trainer(net.collect_params(), "adam",
                                    {"learning_rate": 0.01}))
    loader = DataLoader(ArrayDataset(X, Y), batch_size=32)
    est.fit(loader, val_data=loader, epochs=4,
            event_handlers=[LoggingHandler(log_interval=100)])
    name, acc = est.val_metrics[0].get()
    assert acc > 0.5


def test_voc_map_metrics_hand_computed():
    """AP values validated against hand-computed PR curves."""
    from mxnet_tpu.metric import (VOC07MApMetric, VOCMApMetric,
                                  COCODetectionMetric)
    gt = onp.array([[[0, 0, 10, 10], [20, 20, 30, 30]]], "float64")
    gtl = onp.array([[0, 0]], "float64")
    pred = onp.array([[[0, 0, 10, 10], [50, 50, 60, 60]]], "float64")
    pl = onp.array([[0, 0]], "float64")
    ps = onp.array([[0.9, 0.8]], "float64")

    m = VOCMApMetric(iou_thresh=0.5)
    m.update(pred, pl, ps, gt, gtl)
    assert abs(m.get()[1] - 0.5) < 1e-9          # area under PR
    m7 = VOC07MApMetric(iou_thresh=0.5)
    m7.update(pred, pl, ps, gt, gtl)
    assert abs(m7.get()[1] - 6.0 / 11.0) < 1e-9  # 11-point

    # perfect detections -> 1.0 at every IoU threshold
    c = COCODetectionMetric()
    c.update(gt, gtl, onp.array([[0.9, 0.8]]), gt, gtl)
    names, vals = c.get()
    assert vals[0] == 1.0 and vals[1] == 1.0

    # difficult gt: its detection is ignored, not a FP
    m3 = VOCMApMetric()
    m3.update(pred, pl, ps, gt, gtl, onp.array([[0, 1]], "float64"))
    assert m3.get()[1] == 1.0

    # padded rows (label < 0) are ignored
    m4 = VOCMApMetric()
    gt_pad = onp.array([[[0, 0, 10, 10], [0, 0, 0, 0]]], "float64")
    gtl_pad = onp.array([[0, -1]], "float64")
    m4.update(pred, pl, ps, gt_pad, gtl_pad)
    assert m4.get()[1] == 1.0

    # class_names -> per-class report with mean last
    m5 = VOCMApMetric(class_names=["a", "b"])
    m5.update(pred, pl, ps, gt, gtl)
    names, vals = m5.get()
    assert names[-1] == "mAP" and abs(vals[-1] - 0.5) < 1e-9


def test_metric_mcc_custom_create():
    from mxnet_tpu import metric as mmod
    m = mmod.MCC()
    m.update([nd.array([1, 0, 1, 1])], [nd.array([0.9, 0.2, 0.8, 0.3])])
    # tp=2 fp=0 fn=1 tn=1 -> mcc = (2*1-0*1)/sqrt(2*3*1*2) = 2/sqrt(12)
    assert abs(m.get()[1] - 2.0 / (12 ** 0.5)) < 1e-9

    cm = mmod.create(lambda l, p: float(onp.abs(l - p).sum()))
    cm.update([nd.array([1.0, 2.0])], [nd.array([1.5, 2.0])])
    assert abs(cm.get()[1] - 0.5) < 1e-9
    assert mmod.create("mcc").name == "mcc"


def _write_ppm(path, img):
    h, w = img.shape[:2]
    with open(path, "wb") as f:
        f.write(b"P6\n%d %d\n255\n" % (w, h))
        f.write(img.astype("uint8").tobytes())


def test_voc_detection_dataset(tmp_path):
    """VOC XML tree -> (image, (N,6) label) with 1-based->0-based boxes."""
    base = tmp_path / "VOC2007"
    for d in ("ImageSets/Main", "Annotations", "JPEGImages"):
        (base / d).mkdir(parents=True)
    (base / "ImageSets/Main/trainval.txt").write_text("000001\n")
    (base / "Annotations/000001.xml").write_text("""
<annotation><size><width>32</width><height>24</height></size>
 <object><name>dog</name><difficult>0</difficult>
  <bndbox><xmin>2</xmin><ymin>3</ymin><xmax>11</xmax><ymax>13</ymax></bndbox>
 </object>
 <object><name>person</name><difficult>1</difficult>
  <bndbox><xmin>5</xmin><ymin>6</ymin><xmax>20</xmax><ymax>21</ymax></bndbox>
 </object>
 <object><name>notaclass</name>
  <bndbox><xmin>1</xmin><ymin>1</ymin><xmax>2</xmax><ymax>2</ymax></bndbox>
 </object>
</annotation>""")
    rng = onp.random.RandomState(0)
    _write_ppm(str(base / "JPEGImages/000001.ppm"),
               rng.randint(0, 255, (24, 32, 3)))

    from mxnet_tpu.gluon.data.vision import VOCDetection
    ds = VOCDetection(str(tmp_path), splits=((2007, "trainval"),))
    assert len(ds) == 1 and len(ds.classes) == 20
    img, label = ds[0]
    assert img.shape == (24, 32, 3)
    assert label.shape == (2, 6)          # unknown class dropped
    dog = ds.classes.index("dog")
    person = ds.classes.index("person")
    assert label[0].tolist() == [1.0, 2.0, 10.0, 12.0, float(dog), 0.0]
    assert label[1][4] == person and label[1][5] == 1.0


def test_coco_detection_dataset(tmp_path):
    import json as _json
    (tmp_path / "annotations").mkdir()
    (tmp_path / "val").mkdir()
    rng = onp.random.RandomState(0)
    _write_ppm(str(tmp_path / "val/img1.ppm"), rng.randint(0, 255, (20, 30, 3)))
    ann = {
        "images": [{"id": 7, "file_name": "img1.ppm", "width": 30,
                    "height": 20},
                   {"id": 8, "file_name": "img2.ppm", "width": 30,
                    "height": 20}],
        "categories": [{"id": 17, "name": "cat"}, {"id": 3, "name": "car"}],
        "annotations": [
            {"image_id": 7, "category_id": 17, "bbox": [4, 5, 10, 8],
             "area": 80, "iscrowd": 0},
            {"image_id": 7, "category_id": 3, "bbox": [1, 2, 5, 5],
             "area": 25, "iscrowd": 1},
        ],
    }
    (tmp_path / "annotations/instances_val.json").write_text(
        _json.dumps(ann))
    from mxnet_tpu.gluon.data.vision import COCODetection
    ds = COCODetection(str(tmp_path), splits=("instances_val",))
    assert ds.classes == ["car", "cat"]    # sorted by COCO category id
    assert len(ds) == 1                    # skip_empty drops img2
    img, label = ds[0]
    assert img.shape == (20, 30, 3) and label.shape == (2, 6)
    cat_row = label[label[:, 4] == 1][0]   # 'cat' remapped to contiguous 1
    assert cat_row.tolist() == [4.0, 5.0, 14.0, 13.0, 1.0, 0.0]
    crowd_row = label[label[:, 4] == 0][0]
    assert crowd_row[5] == 1.0             # iscrowd -> difficult


def test_im2rec_roundtrip(tmp_path):
    """im2rec --make-list + pack -> ImageRecordIter reads the batches."""
    import subprocess
    import sys as _sys
    root = tmp_path / "imgs"
    rng = onp.random.RandomState(0)
    for cls in ("a", "b"):
        (root / cls).mkdir(parents=True)
        for i in range(3):
            onp.save(root / cls / f"{i}.npy",
                     rng.randint(0, 255, (16, 16, 3)).astype("uint8"))
    prefix = str(tmp_path / "data")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    # pin the child to CPU: without this it inherits the host's default
    # platform, and on a TPU host a chip belongs to one process
    env = dict(os.environ, PYTHONPATH=repo, JAX_PLATFORMS="cpu")
    for cmd in ([_sys.executable, os.path.join(repo, "tools", "im2rec.py"),
                 prefix, str(root), "--make-list"],
                [_sys.executable, os.path.join(repo, "tools", "im2rec.py"),
                 prefix, str(root)]):
        res = subprocess.run(cmd, capture_output=True, text=True, env=env,
                             timeout=120)
        assert res.returncode == 0, res.stdout + res.stderr
    from mxnet_tpu.io import ImageRecordIter
    it = ImageRecordIter(path_imgrec=prefix + ".rec", data_shape=(3, 16, 16),
                         batch_size=2)
    batches = list(it)
    assert len(batches) == 3
    assert batches[0].data[0].shape == (2, 3, 16, 16)
    labels = sorted(float(x) for b in batches for x in
                    b.label[0].asnumpy().ravel())
    assert labels == [0.0, 0.0, 0.0, 1.0, 1.0, 1.0]
