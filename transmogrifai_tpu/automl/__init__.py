from transmogrifai_tpu.automl.transmogrify import transmogrify, TransmogrifierDefaults
# imported for its stage classes: a model-only process (`cli serve`,
# `WorkflowModel.load`) populates the StageRegistry by importing this
# package, and SanityChecker sits in every default pipeline
from transmogrifai_tpu.automl import sanity_checker  # noqa: F401

__all__ = ["transmogrify", "TransmogrifierDefaults"]
