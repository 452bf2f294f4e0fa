from . import brumby
from . import dit
from . import jamba
from . import llama
from . import mixtral
from . import resnet

__all__ = ["brumby", "dit", "jamba", "llama", "mixtral", "resnet"]
