"""Flax model zoo — TPU-native re-expression of ``fedml_api/model``.

All modules share one calling convention: ``module.apply(variables, x,
train=bool)`` with NHWC image layout (TPU-friendly; the reference uses torch
NCHW). ``create_model`` mirrors the reference's experiment-level factory
(fedml_experiments/distributed/fedavg/main_fedavg.py:229-266).
"""

from fedml_tpu.models.lr import LogisticRegression
from fedml_tpu.models.cnn import CNN_DropOut


def create_model(model_name: str, output_dim: int = 10, **kw):
    """Model factory with reference naming (main_fedavg.py:229-266)."""
    if model_name == "lr":
        return LogisticRegression(num_classes=output_dim)
    if model_name == "cnn":
        return CNN_DropOut(only_digits=(output_dim == 10))
    if model_name in ("resnet18_gn", "resnet18"):
        from fedml_tpu.models.resnet_gn import resnet18_gn
        return resnet18_gn(num_classes=output_dim, **kw)
    if model_name == "resnet56":
        from fedml_tpu.models.resnet import resnet56
        return resnet56(num_classes=output_dim, **kw)
    if model_name == "resnet110":
        from fedml_tpu.models.resnet import resnet110
        return resnet110(num_classes=output_dim, **kw)
    if model_name == "mobilenet":
        from fedml_tpu.models.mobilenet import MobileNet
        return MobileNet(num_classes=output_dim, **kw)
    if model_name == "mobilenet_v3":
        from fedml_tpu.models.mobilenet_v3 import MobileNetV3
        return MobileNetV3(num_classes=output_dim, **kw)
    if model_name == "rnn":
        from fedml_tpu.models.rnn import RNN_OriginalFedAvg
        return RNN_OriginalFedAvg(**kw)
    if model_name == "rnn_seq":
        # per-position scoring over output_dim chars — the variant the
        # shakespeare/fed_shakespeare loaders need: both emit full shifted
        # target sequences [N, T] for the per-token nwp head (data/leaf.py
        # convert, data/tff_h5.py), so the LM must score every step
        from fedml_tpu.models.rnn import RNN_OriginalFedAvg
        return RNN_OriginalFedAvg(
            **{"vocab_size": output_dim, "seq_output": True, **kw})
    if model_name == "rnn_stackoverflow":
        from fedml_tpu.models.rnn import RNN_StackOverflow
        return RNN_StackOverflow(**kw)
    if model_name == "transformer":
        from fedml_tpu.models.transformer import TransformerLM
        return TransformerLM(vocab_size=output_dim, **kw)
    if model_name == "sambay":
        # Phi-4-mini-flash-reasoning's hybrid decoder; output_dim is the
        # rows of the (tied) embedding held here
        from fedml_tpu.models.sambay import SambaYLM
        if "layer_ids" in kw:
            kw = {**kw, "layer_ids": tuple(kw["layer_ids"])}
        return SambaYLM(vocab_size=output_dim, **kw)
    if model_name == "lfm2_moe":
        # LFM2-8B-A1B's hybrid decoder (short convolutions, grouped-query
        # attention, routed experts); output_dim is the rows of the (tied)
        # embedding held here
        from fedml_tpu.models.lfm2_moe import Lfm2MoeLM
        for name in ("layer_ids", "layer_types", "experts_held"):
            if name in kw:
                kw = {**kw, name: tuple(kw[name])}
        return Lfm2MoeLM(vocab_size=output_dim, **kw)
    if model_name == "granite_hybrid":
        # granite-4.0-h-micro's hybrid decoder (Mamba-2 layers beside NoPE
        # grouped-query attention); output_dim is the rows of the (tied)
        # embedding held here
        from fedml_tpu.models.granite_hybrid import GraniteHybridLM
        for name in ("layer_ids", "layer_types"):
            if name in kw:
                kw = {**kw, name: tuple(kw[name])}
        return GraniteHybridLM(vocab_size=output_dim, **kw)
    if model_name == "deepseek_v3":
        # kanana-2-30b-a3b-instruct-2601's decoder (latent attention, routed
        # experts beside shared ones); output_dim is the rows held of the
        # embedding and of the untied head
        from fedml_tpu.models.deepseek_v3 import DeepseekV3LM
        for name in ("layer_ids", "experts_held"):
            if name in kw:
                kw = {**kw, name: tuple(kw[name])}
        return DeepseekV3LM(vocab_size=output_dim, **kw)
    if model_name in ("vgg11", "vgg13", "vgg16", "vgg19"):
        from fedml_tpu.models.vgg import VGG
        return VGG(arch=model_name, num_classes=output_dim, **kw)
    if model_name.startswith("efficientnet"):
        from fedml_tpu.models.efficientnet import efficientnet
        return efficientnet(model_name, num_classes=output_dim)
    if model_name == "resnet8_gkt":
        from fedml_tpu.models.resnet_gkt import resnet8_56
        return resnet8_56(num_classes=output_dim)
    if model_name == "resnet56_gkt_server":
        from fedml_tpu.models.resnet_gkt import resnet56_server
        return resnet56_server(num_classes=output_dim)
    if model_name == "segnet":
        from fedml_tpu.models.segnet import SegNet
        return SegNet(num_classes=output_dim, **kw)
    if model_name == "darts":
        from fedml_tpu.models.darts import DartsNetwork
        return DartsNetwork(num_classes=output_dim, **kw)
    raise ValueError(f"unknown model: {model_name!r}")
