from mpi4dl_tpu.models.resnet import get_resnet_v1, get_resnet_v2, get_resnet
from mpi4dl_tpu.models.amoebanet import amoebanetd
from mpi4dl_tpu.models.lfm2 import lfm2_moe
from mpi4dl_tpu.models import deepseek_v3  # the module: its builder has its name
from mpi4dl_tpu.models import granitemoehybrid  # likewise
from mpi4dl_tpu.models import keye_vl2  # likewise
from mpi4dl_tpu.models import ouro  # likewise
from mpi4dl_tpu.models.seqblock import SeqBlock, make_seq_cp_train_step

__all__ = [
    "get_resnet_v1", "get_resnet_v2", "get_resnet", "amoebanetd", "lfm2_moe",
    "SeqBlock", "make_seq_cp_train_step", "MODELS", "input_kind", "build_model",
]


def _resnet(cfg, in_shape):
    from mpi4dl_tpu.utils import get_depth

    return get_resnet(
        in_shape,
        depth=get_depth(2, cfg.num_layers),
        num_classes=cfg.num_classes,
        version=2,
        softmax_in_model=cfg.softmax_in_model,
    )


def _amoebanet(cfg, in_shape):
    return amoebanetd(
        in_shape,
        num_classes=cfg.num_classes,
        num_layers=cfg.num_layers,
        num_filters=cfg.num_filters,
    )


def _token_model(builder, routed: bool = True):
    """A token model's builder from the flags that state its cut: the layers
    and the vocabulary's rows, and, for a ``routed`` model, the experts this
    process holds (a model without routed experts is not handed them)."""
    def build(cfg, in_shape):
        experts = ({"experts_held": cfg.experts_held,
                    "expert_first": cfg.expert_first} if routed else {})
        return builder(
            in_shape,
            num_layers=cfg.num_layers,
            vocab_size=cfg.vocab_size,
            compute_dtype=cfg.compute_dtype,
            **experts,
        )
    return build


# ``--model`` -> (what a sample is, the builder).  A sample is an ``image``
# (``[H, W, 3]`` floats, one class) or ``tokens`` (``[S]`` int32 ids, the next
# id at every position): the loader (``data.make_dataset``), the input's shape
# (``ParallelConfig.sample_shape``) and the engines that refuse a sequence
# (``benchmarks/common``) read it here, so a new model is entered here alone.
MODELS = {
    "resnet": ("image", _resnet),
    "amoebanet": ("image", _amoebanet),
    "lfm2_moe": ("tokens", _token_model(lfm2_moe)),
    "deepseek_v3": ("tokens", _token_model(deepseek_v3.deepseek_v3)),
    "granitemoehybrid": ("tokens", _token_model(
        granitemoehybrid.granitemoehybrid, routed=False)),
    "keye_vl2": ("tokens", _token_model(keye_vl2.keye_vl2)),
    "ouro": ("tokens", _token_model(ouro.ouro, routed=False)),
}


def input_kind(model: str) -> str:
    """``image`` or ``tokens``: what a sample of ``--model`` is."""
    if model not in MODELS:
        raise ValueError(f"unknown model {model!r}")
    return MODELS[model][0]


def build_model(cfg):
    """Build the model named by cfg.model at cfg's geometry (the dispatch each
    reference benchmark script performs inline).

    For resnet, ``cfg.num_layers`` is the block-count n of the v2 depth
    formula 9n+2 (reference hardcodes n=12 → ResNet-110-v2 per benchmark,
    benchmark_resnet_sp.py:161-163; pass --num-layers 12 for parity).  For
    amoebanet it is the NAS cell count as in the reference parser.  For the
    token models (``[B, S]`` ids in: lfm2_moe, deepseek_v3, granitemoehybrid,
    keye_vl2, ouro)
    it is the layers as run; the vocabulary rows and, where the model has
    routed experts, the experts held come from their own flags."""
    input_kind(cfg.model)  # an unknown model is refused before its shape is asked
    in_shape = (cfg.batch_size // cfg.parts, *cfg.sample_shape)
    return MODELS[cfg.model][1](cfg, in_shape)
