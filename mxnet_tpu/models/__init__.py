"""Model zoo: Symbol builders for the reference's example networks.

Mirrors the capability of ``example/image-classification/symbols/`` in the
reference (mlp, lenet, alexnet, vgg, resnet, resnext, googlenet,
inception-bn, inception-v3, inception-resnet-v2) plus the bucketing LSTM
language model (``example/rnn/lstm_bucketing.py``), a transformer, a
latent-attention mixture-of-experts language model (``glm-moe``) and a
hybrid whose blocks mix by a delta rule with a state along the sequence
or by latent attention (``bailing-hybrid``), and a looped language model
whose one stack of layers runs several times on shared weights, with an
exit gate after every pass (``loop-lm``), and a hybrid whose blocks mix
by a gated short convolution or by grouped-query attention, with routed
experts and no shared one (``lfm2-moe``), and one whose layers attend in
a sliding window or over the whole sequence, gated a head, with routed
experts and a shared one (``laguna``).
Architectures are standard published networks, written fresh in
mxnet_tpu Symbol idiom; the graphs compile to single XLA computations.

Use :func:`get_symbol`::

    sym = mx.models.get_symbol("resnet-50", num_classes=1000)
"""
from . import mlp
from . import lenet
from . import alexnet
from . import vgg
from . import resnet
from . import inception_bn
from . import inception_v3
from . import inception_resnet_v2
from . import googlenet
from . import lstm_lm
from . import resnext
from . import transformer
from . import glm_moe
from . import bailing_hybrid
from . import loop_lm
from . import lfm2_moe
from . import laguna

__all__ = ["get_symbol", "mlp", "lenet", "alexnet", "vgg", "resnet",
           "resnext", "googlenet", "inception_bn", "inception_v3",
           "inception_resnet_v2", "lstm_lm", "transformer", "glm_moe",
           "bailing_hybrid", "loop_lm", "lfm2_moe", "laguna"]

_BUILDERS = {
    "mlp": mlp.get_symbol,
    "lenet": lenet.get_symbol,
    "alexnet": alexnet.get_symbol,
    "googlenet": googlenet.get_symbol,
    "inception-bn": inception_bn.get_symbol,
    "inception-v3": inception_v3.get_symbol,
    "inception-resnet-v2": inception_resnet_v2.get_symbol,
    "transformer": transformer.get_symbol,
    "gpt": transformer.get_symbol,
    "glm-moe": glm_moe.get_symbol,
    "bailing-hybrid": bailing_hybrid.get_symbol,
    "loop-lm": loop_lm.get_symbol,
    "lfm2-moe": lfm2_moe.get_symbol,
    "laguna": laguna.get_symbol,
}


def get_symbol(network, num_classes=1000, **kwargs):
    """Build a named network Symbol.

    ``network`` may be a plain name (``"alexnet"``) or a name-depth form
    (``"resnet-50"``, ``"vgg-16"``) matching the reference's
    ``--network`` CLI strings.
    """
    if network in _BUILDERS:
        return _BUILDERS[network](num_classes=num_classes, **kwargs)
    if network.startswith("resnext"):
        depth = int(network.split("-")[1]) if "-" in network else \
            int(kwargs.pop("num_layers", 50))
        return resnext.get_symbol(num_classes=num_classes,
                                  num_layers=depth, **kwargs)
    if network.startswith("resnet"):
        depth = int(network.split("-")[1]) if "-" in network else \
            int(kwargs.pop("num_layers", 50))
        return resnet.get_symbol(num_classes=num_classes, num_layers=depth,
                                 **kwargs)
    if network.startswith("vgg"):
        depth = int(network.split("-")[1]) if "-" in network else \
            int(kwargs.pop("num_layers", 16))
        return vgg.get_symbol(num_classes=num_classes, num_layers=depth,
                              **kwargs)
    raise ValueError("unknown network %r (have %s, resnet-N, resnext-N, "
                     "vgg-N)" % (network, sorted(_BUILDERS)))
