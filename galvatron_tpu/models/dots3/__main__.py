from galvatron_tpu.models.dots3 import main

raise SystemExit(main())
