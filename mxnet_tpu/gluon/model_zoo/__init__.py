"""Predefined and pretrained models
(reference: python/mxnet/gluon/model_zoo/)."""
from . import model_store
from . import vision
from . import gpt
from . import qwen3_next
from . import kimi_linear
from . import lfm2_moe

from .vision import get_model
from .gpt import GPTDecoder, get_gpt
from .qwen3_next import Qwen3NextDecoder, get_qwen3_next
from .kimi_linear import KimiLinearDecoder, get_kimi_linear
from .lfm2_moe import Lfm2MoeDecoder, get_lfm2_moe
