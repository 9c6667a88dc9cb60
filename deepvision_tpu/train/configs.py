"""Per-model training configs mirroring the reference's ``training_config``
(ref: ResNet/pytorch/train.py:26-215; LeNet/pytorch/train.py). The PyTorch
configs are the accuracy-bearing ones (SURVEY §7 "hard parts" #7) and are
treated as canonical; paper-quote comments preserved in spirit via the ref
citations above each entry.

``input_size`` is the train-time crop; ``image_key`` datasets are wired by
the CLI (train.py at the repo root).
"""

from __future__ import annotations

TRAINING_CONFIG: dict[str, dict] = {
    # ref: LeNet/pytorch/train.py:18-30 — batch 64, Adam 1e-3, plateau, 50ep
    "lenet5": {
        "precision": "f32",
        "batch_size": 64,
        "input_size": 32,
        "channels": 1,
        "num_classes": 10,
        "dataset": "mnist",
        "optimizer": "adam",
        "optimizer_params": {"lr": 1e-3},
        "scheduler": "plateau",
        "scheduler_params": {"factor": 0.1, "mode": "max"},
        "total_epochs": 50,
    },
    # ref: ResNet/pytorch/train.py:27-51 (SGD 0.01/0.9/5e-4, plateau max)
    "alexnet1": {
        "precision": "bf16",
        "augment": "pt",
        "batch_size": 128,
        "input_size": 224,
        "optimizer": "sgd",
        "optimizer_params": {"lr": 0.01, "momentum": 0.9,
                             "weight_decay": 5e-4},
        "scheduler": "plateau",
        "scheduler_params": {"factor": 0.1, "mode": "max"},
        "total_epochs": 200,
    },
    # ref: train.py:52-73
    "alexnet2": {
        "precision": "bf16",
        "augment": "pt",
        "batch_size": 128,
        "input_size": 224,
        "optimizer": "sgd",
        "optimizer_params": {"lr": 0.01, "momentum": 0.9,
                             "weight_decay": 5e-4},
        "scheduler": "plateau",
        "scheduler_params": {"factor": 0.1, "mode": "max"},
        "total_epochs": 200,
    },
    # ref: train.py:74-100 (StepLR 10/0.5)
    "vgg16": {
        "precision": "bf16",
        "augment": "pt",
        "batch_size": 128,
        "input_size": 224,
        "optimizer": "sgd",
        "optimizer_params": {"lr": 0.01, "momentum": 0.9,
                             "weight_decay": 5e-4},
        "scheduler": "step",
        "scheduler_params": {"step_size": 10, "gamma": 0.5},
        "total_epochs": 200,
    },
    # ref: train.py:101-117
    "vgg19": {
        "precision": "bf16",
        "augment": "pt",
        "batch_size": 64,
        "input_size": 224,
        "optimizer": "sgd",
        "optimizer_params": {"lr": 0.01, "momentum": 0.9,
                             "weight_decay": 5e-4},
        "scheduler": "step",
        "scheduler_params": {"step_size": 10, "gamma": 0.5},
        "total_epochs": 200,
    },
    # ref: train.py:118-136 (poly decay lambda)
    "inception1": {
        "precision": "bf16",
        "augment": "pt",
        "batch_size": 128,
        "input_size": 224,
        "optimizer": "sgd",
        "optimizer_params": {"lr": 0.01, "momentum": 0.9,
                             "weight_decay": 2e-4},
        "scheduler": "inception_poly",
        "total_epochs": 200,
    },
    # ref: train.py:137-163 (SGD 0.1/0.9/1e-4, plateau max, batch 256)
    "resnet34": {
        "precision": "bf16",
        "augment": "pt",
        "batch_size": 256,
        "input_size": 224,
        "optimizer": "sgd",
        "optimizer_params": {"lr": 0.1, "momentum": 0.9,
                             "weight_decay": 1e-4},
        "scheduler": "plateau",
        "scheduler_params": {"factor": 0.1, "mode": "max"},
        "total_epochs": 200,
        # MXU-friendly space-to-depth 7x7/2 stem: identical parameter
        # pytree + numerics (models/resnet._Conv7S2D), +2.6% measured
        # img/s on v5e; needs even H/W (all ResNet inputs are 224)
        "model_kwargs": {"s2d_stem": True},
    },
    # ref: train.py:164-180 — the north-star accuracy config (73.93% top-1)
    "resnet50": {
        "precision": "bf16",
        "augment": "pt",
        "batch_size": 256,
        "input_size": 224,
        "optimizer": "sgd",
        "optimizer_params": {"lr": 0.1, "momentum": 0.9,
                             "weight_decay": 1e-4},
        "scheduler": "plateau",
        "scheduler_params": {"factor": 0.1, "mode": "max"},
        "total_epochs": 200,
        # MXU-friendly space-to-depth 7x7/2 stem: identical parameter
        # pytree + numerics (models/resnet._Conv7S2D), +2.6% measured
        # img/s on v5e; needs even H/W (all ResNet inputs are 224)
        "model_kwargs": {"s2d_stem": True},
    },
    "resnet152": {
        "precision": "bf16",
        # block-boundary remat (models/resnet.ResNet.remat, registry
        # default): trade recompute for the 36-deep stage-3 activation
        # surface — the ISSUE 15 HBM diet for the deepest classifier
        "remat": "block",
        "augment": "pt",
        "batch_size": 256,
        "input_size": 224,
        "optimizer": "sgd",
        "optimizer_params": {"lr": 0.1, "momentum": 0.9,
                             "weight_decay": 1e-4},
        "scheduler": "plateau",
        "scheduler_params": {"factor": 0.1, "mode": "max"},
        "total_epochs": 200,
        # MXU-friendly space-to-depth 7x7/2 stem: identical parameter
        # pytree + numerics (models/resnet._Conv7S2D), +2.6% measured
        # img/s on v5e; needs even H/W (all ResNet inputs are 224)
        "model_kwargs": {"s2d_stem": True},
    },
    "resnet50v2": {
        "precision": "bf16",
        "batch_size": 256,
        "input_size": 224,
        "optimizer": "sgd",
        "optimizer_params": {"lr": 0.1, "momentum": 0.9,
                             "weight_decay": 1e-4},
        "scheduler": "plateau",
        "scheduler_params": {"factor": 0.1, "mode": "max"},
        "total_epochs": 200,
    },
    # ref: train.py:181-214 (RMSprop 0.045/alpha .9/eps 1.0, StepLR 2/0.94)
    "mobilenet1": {
        "precision": "bf16",
        "augment": "pt",
        "batch_size": 128,
        "input_size": 224,
        "optimizer": "rmsprop",
        "optimizer_params": {"lr": 0.045, "alpha": 0.9, "eps": 1.0},
        "scheduler": "step",
        "scheduler_params": {"step_size": 2, "gamma": 0.94},
        "total_epochs": 200,
    },
    # reference WIP — config completed per the ShuffleNet paper (linear decay)
    "shufflenet1": {
        "precision": "bf16",
        "augment": "pt",
        "batch_size": 256,
        "input_size": 224,
        "optimizer": "sgd",
        "optimizer_params": {"lr": 0.1, "momentum": 0.9,
                             "weight_decay": 4e-5},
        "scheduler": "step",
        "scheduler_params": {"step_size": 30, "gamma": 0.1},
        "total_epochs": 120,
    },
    # reference stub — config per Inception V3 paper
    "inception3": {
        "precision": "bf16",
        "batch_size": 128,
        "input_size": 299,
        "optimizer": "rmsprop",
        "optimizer_params": {"lr": 0.045, "alpha": 0.9, "eps": 1.0},
        "scheduler": "step",
        "scheduler_params": {"step_size": 2, "gamma": 0.94},
        "total_epochs": 200,
    },
    # Darknet-53 ImageNet pretraining for the YOLO backbone (paper config;
    # the reference trains detection from scratch and has no pretrain path)
    "darknet53": {
        "precision": "bf16",
        "batch_size": 128,
        "input_size": 256,
        "optimizer": "sgd",
        "optimizer_params": {"lr": 0.1, "momentum": 0.9,
                             "weight_decay": 5e-4},
        "scheduler": "step",
        "scheduler_params": {"step_size": 30, "gamma": 0.1},
        "total_epochs": 120,
    },
    # ref: YOLO/tensorflow/train.py:13-29 — per-replica batch 16, Adam 0.01,
    # /10 plateau on val loss (simulated ReduceLROnPlateau :56-68), 300 ep
    "yolov3": {
        "precision": "bf16",
        "batch_size": 16,
        "input_size": 416,
        "num_classes": 20,  # VOC; 80 for COCO (ref: train.py:14)
        "dataset": "detection",
        "optimizer": "adam",
        "optimizer_params": {"lr": 0.01},
        "scheduler": "plateau",
        "scheduler_params": {"factor": 0.1, "mode": "max", "patience": 10},
        "total_epochs": 300,
    },
    # ref: DCGAN/tensorflow/main.py:13-17,31-32 — batch 256, two Adams
    # 1e-4, 50 epochs, noise dim 100, checkpoint every 2 epochs keep 3
    "dcgan": {
        "precision": "bf16",
        "batch_size": 256,
        "input_size": 28,
        "channels": 1,
        "dataset": "gan_mnist",
        "noise_dim": 100,
        "optimizer": "adam",
        "optimizer_params": {"lr": 1e-4},
        "save_every": 2,
        "total_epochs": 50,
    },
    # ref: CycleGAN/tensorflow/train.py:14-21,122-127 — batch 4 (CLI
    # default), two Adams 2e-4 β1 0.5, LinearDecay to 0 over epochs
    # 100..200, pool 50, λ_cycle 10, λ_id 5
    "cyclegan": {
        "precision": "bf16",
        "batch_size": 4,
        "input_size": 256,
        "dataset": "gan_unpaired",
        "optimizer": "adam",
        "optimizer_params": {"lr": 2e-4, "beta1": 0.5},
        "decay_epochs": 100,
        "save_every": 1,
        "total_epochs": 200,
    },
    # ref: ObjectsAsPoints/tensorflow/train.py:24-57,205-216 — Adam,
    # per-replica batch 16, /10 plateau after 10 stale epochs. The ref's
    # 0.01 default was never trained (loss list empty, run commented out);
    # we deliberately use 1e-3: 0.01 destabilizes penalty-reduced focal
    # loss (the paper itself trains hourglass CenterNet at 2.5e-4).
    "centernet": {
        "precision": "bf16",
        "batch_size": 16,
        "input_size": 256,
        "num_classes": 80,  # MSCOCO (ref model.py:131)
        "dataset": "detection",
        "steps": "centernet",
        "optimizer": "adam",
        "optimizer_params": {"lr": 1e-3},
        "scheduler": "plateau",
        "scheduler_params": {"factor": 0.1, "mode": "max", "patience": 10},
        "total_epochs": 100,
    },
    # ref: Hourglass/tensorflow/train.py:30-44,229-240 — Adam 1e-4 (the
    # paper quote says "rmsprop 2.5e-4" but the code uses Adam), batch 16,
    # /10 plateau on val loss after max_patience=10 stale epochs (:46-58)
    "hourglass104": {
        "batch_size": 16,
        "input_size": 256,
        "num_heatmaps": 16,
        "dataset": "pose",
        "optimizer": "adam",
        "optimizer_params": {"lr": 1e-4},
        # r4 measured plain bf16 crippling this net (synthetic gate
        # loss 74 vs 5.1 at 30 epochs — bf16 rounding compounding
        # through the recursion). ISSUE 15 addressed the mechanism
        # structurally: the residual/cross-stack carrier now accumulates
        # in f32 (models/hourglass.py) with only block internals in
        # bf16, plus dynamic loss scaling as the wide-range heatmap
        # regression's guard — the bf16-vs-f32 twin gate
        # (tests/test_precision.py) pins the trajectory agreement.
        "precision": "bf16_scaled",
        # per-stack remat (models/hourglass.StackedHourglass.remat,
        # registry default): the order-4 recursion x 4 stacks is the
        # deepest activation surface in the zoo
        "remat": "stack",
        # mode "max" on the Trainer's negated val loss (the yolov3
        # convention): lower loss -> higher metric -> improvement
        "scheduler": "plateau",
        "scheduler_params": {"factor": 0.1, "mode": "max", "patience": 10},
        "total_epochs": 100,
    },
    # Keye-VL-2.0-30B-A3B (models/transformer.py): a ViT tower, sparse
    # attention behind a learned indexer, 128 experts top-8. Published
    # widths; the three entries differ in how much of the model one
    # process holds. Adam (f32 parameter, gradient and two moments: 16
    # bytes a parameter), a linear warm-up over 2,000 steps to a constant
    # 1e-4. Adam's first steps are about lr x sign(gradient) whatever the
    # gradient's size: with no warm-up, tens of steps at 1e-4 or at 1e-5
    # on a small resident set built up a part of the stream that every
    # token shares, and the routing collapsed with it (one chip's experts
    # drew 5-7 x their share within 35 steps; PERF.md, PR 28).
    "keye_vl2": {
        "precision": "bf16",
        "batch_size": 1,
        "input_size": 448,
        "text_len": 7936,
        "dataset": "vlm",
        "steps": "vlm",
        "optimizer": "adam",
        "optimizer_params": {"lr": 1e-4},
        "scheduler": "warmup",
        "scheduler_params": {"warmup_steps": 2000},
        "total_epochs": 1,
    },
    # one chip's share of an 8-chip expert-parallel layer: 16 of 128
    # experts, 18,992 of 151,936 vocabulary rows, 5 + 6 layers (the
    # benchmark's keye_vl2_30b_a3b.train_seq8k)
    "keye_vl2_ep8": {
        "precision": "bf16",
        "batch_size": 2,
        "input_size": 448,
        "text_len": 7936,
        "dataset": "vlm",
        "steps": "vlm",
        "optimizer": "adam",
        "optimizer_params": {"lr": 1e-4},
        "scheduler": "warmup",
        "scheduler_params": {"warmup_steps": 2000},
        "total_epochs": 1,
    },
    # CPU-sized preset of the same layers (tests, smoke runs)
    "keye_vl2_tiny": {
        "precision": "bf16",
        "batch_size": 8,
        "input_size": 16,
        "text_len": 60,
        "dataset": "vlm",
        "steps": "vlm",
        "optimizer": "adam",
        "optimizer_params": {"lr": 1e-3},
        "total_epochs": 2,
    },
    # kanana-2-30b-a3b (models/latent_moe.py, DeepSeek-V3's layout):
    # latent attention, 128 sigmoid-routed experts top-6 behind a
    # balancing bias, two shared experts, a leading dense layer; text
    # only. Published widths; the three entries differ in how much of
    # the model one process holds. Adam and the warm-up as the sibling
    # family's, for its reasons; ``text_len`` counts a document's ids
    # (one more than its positions: position i predicts id i + 1).
    "kanana2": {
        "precision": "bf16",
        "batch_size": 1,
        "text_len": 8193,
        "dataset": "lm",
        "steps": "lm",
        "optimizer": "adam",
        "optimizer_params": {"lr": 1e-4},
        "scheduler": "warmup",
        "scheduler_params": {"warmup_steps": 2000},
        "total_epochs": 1,
    },
    # one chip's share of an 8-chip expert-parallel layer: 16 of 128
    # experts, 16,032 of 128,256 vocabulary rows, the dense layer and 5
    # expert layers (the benchmark's kanana2_30b_a3b.train_text8k)
    "kanana2_ep8": {
        "precision": "bf16",
        "batch_size": 2,
        "text_len": 8193,
        "dataset": "lm",
        "steps": "lm",
        "optimizer": "adam",
        "optimizer_params": {"lr": 1e-4},
        "scheduler": "warmup",
        "scheduler_params": {"warmup_steps": 2000},
        "total_epochs": 1,
    },
    # CPU-sized preset of the same layers (tests, smoke runs)
    "kanana2_tiny": {
        "precision": "bf16",
        "batch_size": 8,
        "text_len": 65,
        "dataset": "lm",
        "steps": "lm",
        "optimizer": "adam",
        "optimizer_params": {"lr": 1e-3},
        "total_epochs": 2,
    },
    # Xing4.0-29B-A4B (models/hyper_latent.py): kanana's layers on a
    # residual of 4 streams mixed by manifold-constrained
    # hyper-connections, a compressed query, yarn rotary, 64 experts
    # top-4 with one shared expert, two leading dense layers and one
    # multi-token-prediction module; the optimiser is kanana's. A
    # document is 4,097 ids: 4,096 positions (the config's original
    # pre-training length), next-token and second-next-token labels.
    "xing4": {
        "precision": "bf16",
        "batch_size": 1,
        "text_len": 4097,
        "dataset": "lm",
        "steps": "lm",
        "optimizer": "adam",
        "optimizer_params": {"lr": 1e-4},
        "scheduler": "warmup",
        "scheduler_params": {"warmup_steps": 2000},
        "total_epochs": 1,
    },
    # one chip's share of an 8-chip tensor- and expert-parallel layer: 4
    # of 32 heads, 8 of 64 experts, 16,384 of 131,072 vocabulary rows,
    # one dense and 4 expert blocks and the MTP module (the benchmark's
    # xing4_29b_a4b.train_mtp)
    "xing4_ep8tp8": {
        "precision": "bf16",
        "batch_size": 2,
        "text_len": 4097,
        "dataset": "lm",
        "steps": "lm",
        "optimizer": "adam",
        "optimizer_params": {"lr": 1e-4},
        "scheduler": "warmup",
        "scheduler_params": {"warmup_steps": 2000},
        "total_epochs": 1,
    },
    "xing4_tiny": {
        "precision": "bf16",
        "batch_size": 8,
        "text_len": 65,
        "dataset": "lm",
        "steps": "lm",
        "optimizer": "adam",
        "optimizer_params": {"lr": 1e-3},
        "total_epochs": 2,
    },
    # Olmo-Hybrid-7B (models/olmo_hybrid.py) is served, not trained, at
    # its published widths (benchmark cell olmo_hybrid_7b.serve_longprompt);
    # its CPU preset trains on the seeded token set through the chunked
    # delta rule with a plain next-token step (no experts, no bias rule)
    "olmo_hybrid_tiny": {
        "precision": "bf16",
        "batch_size": 8,
        "text_len": 65,
        "dataset": "lm",
        "steps": "dense_lm",
        "optimizer": "adam",
        "optimizer_params": {"lr": 1e-3},
        "total_epochs": 2,
    },
}


def get_config(name: str) -> dict:
    # "<model>_ref" = reference-exact architecture variant (converter
    # parity, e.g. inception1_ref = BN-free BasicConv blocks); trains and
    # evaluates with the base model's config
    base = name
    if name.endswith("_ref") and name[:-4] in TRAINING_CONFIG:
        base = name[:-4]
    # deep copy: callers override nested entries (train.py writes
    # optimizer_params["lr"] from --lr), and a shallow dict() would let
    # those writes contaminate the global table across in-process runs
    import copy

    cfg = copy.deepcopy(TRAINING_CONFIG[base])
    cfg.setdefault("input_size", 224)
    cfg.setdefault("channels", 3)
    cfg.setdefault("num_classes", 1000)
    cfg.setdefault("dataset", "imagenet")
    # numerics policy (ISSUE 15): every shipped entry declares
    # "precision" explicitly (the table is the single source of truth —
    # CLI --precision overrides, nothing else does); the setdefault
    # only covers ad-hoc test configs built outside the table
    cfg.setdefault("precision", "bf16")
    # remat: config declaration wins; else the registry-declared
    # per-model policy (models/registry.model_remat). Folded into
    # model_kwargs so every builder that constructs the model from this
    # config (train.py, evalcheck, ircheck, bench) compiles the policy.
    if "remat" not in cfg:
        # the package import (not bare registry) guarantees the
        # registration side effects ran before the lookup
        import deepvision_tpu.models  # noqa: F401
        from deepvision_tpu.models.registry import model_remat

        cfg["remat"] = model_remat(base)
    if cfg["remat"] is not None:
        mk = cfg.setdefault("model_kwargs", {})
        mk.setdefault("remat", cfg["remat"])
    cfg["name"] = name
    return cfg
