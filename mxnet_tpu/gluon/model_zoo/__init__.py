"""Model zoo (reference: python/mxnet/gluon/model_zoo/)."""
from . import (afmoe, bert, deepseek_v3, kimi_linear, lfm2_moe,  # noqa: F401
               model_store, ouro, sdar, vision)
from .bert import bert_12_768_12, bert_24_1024_16, get_bert_model  # noqa: F401
from .sdar import sdar_moe  # noqa: F401
from .vision import get_model  # noqa: F401
