"""Model zoo (reference: python/mxnet/gluon/model_zoo/)."""
from . import bert, deepseek_v3, model_store, ouro, sdar, vision  # noqa: F401
from .bert import bert_12_768_12, bert_24_1024_16, get_bert_model  # noqa: F401
from .sdar import sdar_moe  # noqa: F401
from .vision import get_model  # noqa: F401
