"""ImageNet-class training CLI — the flagship end-to-end workload
(reference example/image-classification/train_imagenet.py +
common/fit.py:139), driven entirely through the public API:
model-zoo symbol -> ImageRecordIter (native C++ decode pipeline when
built) -> Module.fit with kvstore, lr schedule, Speedometer,
checkpoint/resume.  Pair with tools/launch.py --max-restarts for the
elastic multi-process mode.

Typical uses:
  # real data (RecordIO produced by tools/im2rec)
  python example/image_classification/train_imagenet.py \
      --data-train train.rec --network resnet --num-layers 50 \
      --batch-size 32 --num-epochs 90 --model-prefix ckpt/r50

  # synthetic-data benchmark mode (no IO in the loop)
  python example/image_classification/train_imagenet.py --benchmark 1 \
      --network resnet --num-layers 50 --num-examples 512 --num-epochs 1

  # resume
  ... --model-prefix ckpt/r50 --load-epoch 30
"""
import argparse
import logging
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))

import numpy as np

import mxnet_tpu as mx


def parse_args(argv=None):
    p = argparse.ArgumentParser(
        description="train an image-classification model",
        formatter_class=argparse.ArgumentDefaultsHelpFormatter)
    d = p.add_argument
    d("--network", default="resnet",
      help="model family: resnet | resnet_v1 | resnext | mobilenet | "
           "googlenet | inception_v4 | vgg | alexnet | mlp | lenet")
    d("--num-layers", type=int, default=50,
      help="depth for depth-parameterised families "
           "(resnet/resnet_v1/resnext/vgg)")
    d("--num-classes", type=int, default=1000)
    d("--image-shape", default="3,224,224")
    d("--dtype", default="float32",
      help="float32 | bfloat16 (TPU-native mixed precision)")
    # data
    d("--data-train", default=None, help="training RecordIO (.rec)")
    d("--data-val", default=None, help="validation RecordIO (.rec)")
    d("--benchmark", type=int, default=0,
      help="1 = synthetic device-resident data, no IO in the loop")
    d("--num-examples", type=int, default=1281167,
      help="examples per epoch (drives the lr schedule)")
    d("--data-nthreads", type=int, default=os.cpu_count() or 4,
      help="decode threads for the native pipeline")
    d("--rand-crop", type=int, default=1)
    d("--rand-mirror", type=int, default=1)
    # optimizer
    d("--batch-size", type=int, default=32)
    d("--num-epochs", type=int, default=90)
    d("--lr", type=float, default=0.1)
    d("--lr-factor", type=float, default=0.1)
    d("--lr-step-epochs", default="30,60,80",
      help="epochs at which lr decays by --lr-factor")
    d("--mom", type=float, default=0.9)
    d("--wd", type=float, default=1e-4)
    d("--optimizer", default="sgd")
    # infra
    d("--kv-store", default="device",
      help="local | device | tpu | dist_sync | dist_device_sync | "
         "dist_async")
    d("--model-prefix", default=None, help="checkpoint path prefix")
    d("--load-epoch", type=int, default=None,
      help="resume from this checkpoint epoch")
    d("--disp-batches", type=int, default=20,
      help="Speedometer logging period")
    d("--top-k", type=int, default=0,
      help="also report top-k accuracy when > 0")
    d("--monitor", type=int, default=0,
      help="install a Monitor with this stat period")
    return p.parse_args(argv)


def get_network(args):
    from mxnet_tpu import models
    shape = tuple(int(x) for x in args.image_shape.split(","))
    fam = args.network.lower()
    kw = dict(num_classes=args.num_classes, dtype=args.dtype)
    if fam == "resnet":
        return models.resnet.get_symbol(
            num_layers=args.num_layers, image_shape=args.image_shape, **kw), \
            shape
    if fam == "resnet_v1":
        return models.resnet_v1.get_symbol(num_layers=args.num_layers,
                                           **kw), shape
    if fam == "resnext":
        return models.resnext.get_symbol(num_layers=args.num_layers,
                                         **kw), shape
    if fam == "mobilenet":
        return models.mobilenet.get_symbol(**kw), shape
    if fam == "googlenet":
        return models.googlenet.get_symbol(**kw), shape
    if fam == "inception_v4":
        return models.inception_v4.get_symbol(**kw), shape
    if fam == "vgg":
        return models.vgg.get_symbol(num_layers=args.num_layers, **kw), shape
    if fam == "alexnet":
        return models.alexnet.get_symbol(**kw), shape
    if fam == "mlp":
        return models.mlp.get_symbol(num_classes=args.num_classes), shape
    if fam == "lenet":
        return models.lenet.get_symbol(num_classes=args.num_classes), shape
    raise ValueError("unknown --network %r" % args.network)


def data_iters(args, kv, shape):
    """ImageRecordIter pair partitioned across workers (reference
    common/data.py get_rec_iter)."""
    if args.benchmark:
        rs = np.random.RandomState(0)
        n = max(args.batch_size, min(args.num_examples, 4 * args.batch_size))
        x = rs.rand(n, *shape).astype(np.float32)
        y = rs.randint(0, args.num_classes, n).astype(np.float32)
        return mx.io.NDArrayIter(x, y, args.batch_size, shuffle=True,
                                 label_name="softmax_label"), None
    train = mx.io.ImageRecordIter(
        path_imgrec=args.data_train, data_shape=shape,
        batch_size=args.batch_size, shuffle=True,
        rand_crop=bool(args.rand_crop), rand_mirror=bool(args.rand_mirror),
        preprocess_threads=args.data_nthreads,
        num_parts=kv.num_workers, part_index=kv.rank)
    val = None
    if args.data_val:
        val = mx.io.ImageRecordIter(
            path_imgrec=args.data_val, data_shape=shape,
            batch_size=args.batch_size, shuffle=False,
            preprocess_threads=args.data_nthreads,
            num_parts=kv.num_workers, part_index=kv.rank)
    return train, val


def lr_schedule(args, kv):
    """MultiFactor decay at --lr-step-epochs, shifted for resume
    (reference common/fit.py _get_lr_scheduler)."""
    begin = args.load_epoch or 0
    epoch_size = max(args.num_examples // args.batch_size
                     // max(kv.num_workers, 1), 1)
    steps = [int(e) for e in args.lr_step_epochs.split(",") if e.strip()]
    lr = args.lr
    for s in steps:
        if begin >= s:
            lr *= args.lr_factor
    if lr != args.lr:
        logging.info("Adjusted lr to %s for resume at epoch %d", lr, begin)
    remaining = [(s - begin) * epoch_size for s in steps if s > begin]
    sched = mx.lr_scheduler.MultiFactorScheduler(
        remaining, args.lr_factor) if remaining else None
    if sched is not None:
        sched.base_lr = lr
    return lr, sched


def main(argv=None):
    """Train as the command line says (``argv``: sys.argv[1:] when None);
    returns the fitted Module."""
    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)-15s %(message)s")
    args = parse_args(argv)
    if not args.benchmark and not args.data_train:
        raise SystemExit("--data-train is required (or use --benchmark 1)")

    kv = mx.kv.create(args.kv_store)
    net, shape = get_network(args)
    train, val = data_iters(args, kv, shape)
    lr, sched = lr_schedule(args, kv)

    # resume / checkpoint plumbing: rank-qualified prefix like the
    # reference's _save_model/_load_model
    arg_params = aux_params = None
    prefix = args.model_prefix
    if prefix and kv.rank > 0:
        prefix += "-%d" % kv.rank
    if prefix and args.load_epoch is not None:
        _, arg_params, aux_params = mx.model.load_checkpoint(
            prefix, args.load_epoch)
        logging.info("Resumed from %s-%04d.params", prefix, args.load_epoch)
    epoch_cb = mx.callback.do_checkpoint(prefix) if prefix else None
    batch_cb = mx.callback.Speedometer(args.batch_size, args.disp_batches)

    metrics = [mx.metric.Accuracy(), mx.metric.CrossEntropy()]
    if args.top_k > 0:
        metrics.append(mx.metric.TopKAccuracy(top_k=args.top_k))

    opt_params = {"learning_rate": lr, "wd": args.wd}
    if args.optimizer in ("sgd", "nag"):
        opt_params["momentum"] = args.mom
    if sched is not None:
        opt_params["lr_scheduler"] = sched
    if args.dtype == "bfloat16":
        opt_params["multi_precision"] = True

    mon = mx.mon.Monitor(args.monitor, pattern=".*weight") \
        if args.monitor > 0 else None

    # train on the accelerator (the reference's --gpus analog; mxnet's
    # default context is cpu, which would silently run the model on the
    # host).  The host is used only where jax itself was told to:
    # JAX_PLATFORMS=cpu.  Anywhere else a missing accelerator is an error,
    # raised when mx.tpu() is resolved.
    pinned_to_host = os.environ.get("JAX_PLATFORMS", "").lower() == "cpu"
    ctx = mx.cpu() if pinned_to_host else mx.tpu()
    logging.info("training on %s (%s)", ctx, ctx.jax_device)
    mod = mx.mod.Module(net, context=ctx)
    mod.fit(train, eval_data=val,
            eval_metric=mx.metric.CompositeEvalMetric(metrics),
            kvstore=kv, optimizer=args.optimizer,
            optimizer_params=opt_params,
            initializer=mx.init.Xavier(rnd_type="gaussian",
                                       factor_type="in", magnitude=2),
            arg_params=arg_params, aux_params=aux_params,
            begin_epoch=args.load_epoch or 0,
            num_epoch=args.num_epochs,
            batch_end_callback=batch_cb, epoch_end_callback=epoch_cb,
            allow_missing=True, monitor=mon)
    print("train_imagenet OK")
    return mod


if __name__ == "__main__":
    main()
