"""Imports every params module so the registry is fully populated.

Ref: `lingvo/model_imports.py` — here a static import list (cheap; the
dynamic per-prefix import in model_registry handles the common CLI path).
"""

from lingvo_tpu.models.image.params import mnist  # noqa: F401

try:
  from lingvo_tpu.models.lm.params import synthetic_packed_input  # noqa: F401
except ImportError:
  pass
try:
  from lingvo_tpu.models.lm.params import nemotron_h  # noqa: F401
  from lingvo_tpu.models.lm.params import brumby  # noqa: F401
  from lingvo_tpu.models.lm.params import mistral4  # noqa: F401
  from lingvo_tpu.models.lm.params import granite_hybrid  # noqa: F401
  from lingvo_tpu.models.lm.params import phi4flash  # noqa: F401
  from lingvo_tpu.models.lm.params import smallthinker  # noqa: F401
  from lingvo_tpu.models.lm.params import trinity  # noqa: F401
  from lingvo_tpu.models.lm.params import lfm2  # noqa: F401
except ImportError:
  pass
try:
  from lingvo_tpu.models.lm.params import one_billion_wds  # noqa: F401
except ImportError:
  pass
try:
  from lingvo_tpu.models.lm.params import wiki_bert  # noqa: F401
except ImportError:
  pass
try:
  from lingvo_tpu.models.mt.params import wmt14_en_de  # noqa: F401
except ImportError:
  pass
try:
  from lingvo_tpu.models.asr.params import librispeech  # noqa: F401
except ImportError:
  pass

try:
  from lingvo_tpu.models.punctuator.params import codelab  # noqa: F401
except ImportError:
  pass
try:
  from lingvo_tpu.models.milan.params import dual_encoder  # noqa: F401
except ImportError:
  pass
try:
  from lingvo_tpu.models.car.params import kitti  # noqa: F401
except ImportError:
  pass
try:
  from lingvo_tpu.models.car.params import waymo  # noqa: F401
except ImportError:
  pass
